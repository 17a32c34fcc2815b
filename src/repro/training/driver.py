"""Fault-tolerant training driver: step loop + periodic async checkpoints +
bit-exact resume (params, optimizer state, RNG and data cursor are all part
of the checkpoint). A `failure_at` hook simulates a node crash mid-run for
the restart tests; `resume()` continues from the latest checkpoint.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_params
from repro.models.config import ModelConfig
from repro.training.checkpoint import (AsyncCheckpointer, latest_step,
                                       restore_checkpoint)
from repro.training.optim import OptConfig
from repro.training.train_step import make_train_step


class CrashInjected(RuntimeError):
    pass


@dataclass
class TrainerConfig:
    total_steps: int = 50
    ckpt_every: int = 10
    ckpt_dir: str = "/tmp/repro_ckpt"
    seed: int = 0
    log_every: int = 10


class Trainer:
    def __init__(self, cfg: ModelConfig, opt_cfg: OptConfig, data,
                 tcfg: TrainerConfig, *, constrain=None, grad_transform=None,
                 jit_kwargs=None, shardings=None):
        self.cfg, self.opt_cfg, self.data, self.tcfg = cfg, opt_cfg, data, tcfg
        init_fn, step_fn = make_train_step(cfg, opt_cfg, remat=False,
                                           constrain=constrain,
                                           grad_transform=grad_transform)
        self._init_opt = init_fn
        self.train_step = jax.jit(step_fn, **(jit_kwargs or {}))
        self.shardings = shardings
        self.ckpt = AsyncCheckpointer(tcfg.ckpt_dir)
        self.params = None
        self.opt_state = None
        self.step = 0
        self.history = []

    # ------------------------------------------------------------ state ---

    def init(self):
        # float32 master weights; the step casts to cfg.dtype for compute
        self.params = init_params(self.cfg.replace(dtype="float32"),
                                  jax.random.PRNGKey(self.tcfg.seed))
        self.opt_state = self._init_opt(self.params)
        self.step = 0

    def state_tree(self):
        return {"params": self.params, "opt": self.opt_state}

    def resume(self) -> bool:
        last = latest_step(self.tcfg.ckpt_dir)
        if last is None:
            return False
        like = {"params": self.params, "opt": self.opt_state}
        tree, extra = restore_checkpoint(self.tcfg.ckpt_dir, last, like,
                                         shardings=self.shardings)
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step = extra["step"]
        self.data.restore(extra["data"])
        return True

    # -------------------------------------------------------------- run ---

    def run(self, *, failure_at: int | None = None):
        assert self.params is not None, "call init() or resume() first"
        while self.step < self.tcfg.total_steps:
            if failure_at is not None and self.step == failure_at:
                raise CrashInjected(f"injected failure at step {self.step}")
            batch = self.data.next()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt_state, metrics = self.train_step(
                self.params, self.opt_state, batch)
            self.step += 1
            loss = float(metrics["loss"])
            self.history.append(loss)
            if self.step % self.tcfg.log_every == 0:
                print(f"step {self.step}: loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}", flush=True)
            if self.step % self.tcfg.ckpt_every == 0:
                self.ckpt.save(self.step, self.state_tree(),
                               extra={"step": self.step,
                                      "data": self.data.snapshot()})
        self.ckpt.wait()
        return self.history
