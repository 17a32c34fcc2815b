"""Runtime tests: serving engine, checkpoint/restart, straggler mitigation,
data pipeline determinism, optimizers.
"""
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import lm_data
from repro.data.corpus import make_swde_corpus
from repro.distributed.straggler import run_with_stragglers
from repro.models import decode_step, forward, init_params, prefill
from repro.serving.engine import Request, RunTruncated, ServingEngine
from repro.training.checkpoint import (latest_step, restore_checkpoint,
                                       save_checkpoint)
from repro.training.driver import CrashInjected, Trainer, TrainerConfig
from repro.training.optim import OptConfig
from repro.training.train_step import make_train_step


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("qwen2.5-3b").replace(vocab_size=lm_data.VOCAB)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_init_params_in_deployment_dtype(tiny):
    """A bf16 config gets bf16 leaves: the float32 draw rounded once, the
    same values as casting the float32 params."""
    cfg, params32 = tiny
    params16 = init_params(cfg.replace(dtype="bfloat16"), jax.random.PRNGKey(0))
    assert {str(a.dtype) for a in jax.tree.leaves(params16)} == {"bfloat16"}
    for a, b in zip(jax.tree.leaves(params16), jax.tree.leaves(params32)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b.astype(jnp.bfloat16)))


def test_compilation_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache is one fixed directory in the checkout."""
    from repro.launch import compile_cache
    seen = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: seen.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compilation_cache() == "/elsewhere/cache"
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    assert compile_cache.enable_compilation_cache() == want
    assert seen == [("jax_compilation_cache_dir", want)]


# ------------------------------------------------------------- serving -----


def _reference_generate(cfg, params, prompt, n_new):
    """Greedy generation via repeated full forward (oracle)."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _ = forward(cfg, params, {"tokens": jnp.asarray([toks], jnp.int32)})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_reference(tiny):
    cfg, params = tiny
    prompts = [[1, 2, 3, 4], [9, 8, 7], [5, 5, 5, 5, 5], [3, 1], [2, 6, 4]]
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=6, eos_id=-1))
    done = eng.run()
    assert len(done) == len(prompts)
    for i, p in enumerate(prompts):
        want = _reference_generate(cfg, params, p, 6)
        assert done[i].out == want, (i, done[i].out, want)
    # continuous batching actually reused slots (5 requests, 2 slots)
    assert eng.stats["decode_steps"] > 0


def test_engine_eviction_requeues(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=5, eos_id=-1))
    # insert, decode one step, then simulate worker failure
    eng._insert(0, eng.queue.popleft())
    eng._step()
    eng.drain_slot(0)
    assert eng.stats["evictions"] == 1
    done = eng.run()
    assert done[0].retries == 1
    assert done[0].out == _reference_generate(cfg, params, [1, 2, 3], 5)


def test_engine_run_truncation_is_loud(tiny):
    """Exhausting max_steps with work still pending must not read as a
    complete run: strict mode raises, non-strict flags it in stats."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[1, 2, 3], max_new=8, eos_id=-1))
    with pytest.raises(RunTruncated) as exc:
        eng.run(max_steps=2)
    assert eng.stats["truncations"] == 1
    assert len(exc.value.finished) < 3
    # non-strict callers get partial results plus the flag
    eng2 = ServingEngine(cfg, params, slots=1, max_len=32)
    for i in range(3):
        eng2.submit(Request(rid=i, prompt=[1, 2, 3], max_new=8, eos_id=-1))
    done = eng2.run(max_steps=2, strict=False)
    assert eng2.stats["truncations"] == 1 and len(done) < 3
    # the same engine can finish the drain afterwards
    assert len(eng2.run()) == 3


def test_engine_drain_slot_retry_cap(tiny):
    """A persistently failing slot must not requeue forever: past
    max_retries the request fails visibly instead."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=5, eos_id=-1,
                       max_retries=2))
    for _ in range(10):                      # persistent slot failure
        if eng.queue:
            eng._insert(0, eng.queue.popleft())
        if not eng.active:
            break
        eng.drain_slot(0)
    assert 0 in eng.failed and eng.failed[0].error is not None
    assert eng.failed[0].retries == 3        # initial + 2 retries, then fail
    assert eng.stats["failures"] == 1
    assert not eng.queue and not eng.active  # run() would terminate
    assert eng.run() == {}


# ---------------------------------------------------------- checkpoints ----


def test_checkpoint_roundtrip(tmp_path, tiny):
    cfg, params = tiny
    save_checkpoint(tmp_path, 7, {"params": params}, extra={"step": 7})
    assert latest_step(tmp_path) == 7
    tree, extra = restore_checkpoint(tmp_path, 7, {"params": params})
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(tree["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _make_trainer(cfg, tmp, total=12, ckpt_every=4, seed=0):
    corpus = make_swde_corpus()
    stream = lm_data.corpus_token_stream(corpus)
    data = lm_data.LMBatches(stream, batch=2, seq=16)
    tcfg = TrainerConfig(total_steps=total, ckpt_every=ckpt_every,
                         ckpt_dir=str(tmp), seed=seed, log_every=100)
    return Trainer(cfg, OptConfig(lr=1e-3, warmup_steps=2), data, tcfg)


def test_crash_resume_bit_exact(tmp_path, tiny):
    cfg, _ = tiny
    # run A: straight through
    t_a = _make_trainer(cfg, tmp_path / "a")
    t_a.init()
    hist_a = t_a.run()
    # run B: crash at step 6, restart from checkpoint (step 4), continue
    t_b = _make_trainer(cfg, tmp_path / "b")
    t_b.init()
    with pytest.raises(CrashInjected):
        t_b.run(failure_at=6)
    t_b.ckpt.wait()
    t_b2 = _make_trainer(cfg, tmp_path / "b")
    t_b2.init()          # build like-tree for restore
    assert t_b2.resume()
    assert t_b2.step == 4
    t_b2.run()
    # losses from the resumed run must match the uninterrupted run exactly
    np.testing.assert_allclose(hist_a[4:], t_b2.history, rtol=0, atol=0)


# ------------------------------------------------------------ straggler ----


def test_straggler_reissue_completes_faster():
    def work(x):
        time.sleep(0.01)
        return x * x

    slow = lambda wid: 0.4 if wid == 0 else 0.0   # worker 0 is a straggler
    results, stats = run_with_stragglers(range(12), work, n_workers=3,
                                         worker_delay=slow,
                                         deadline_factor=3.0)
    assert results == {i: i * i for i in range(12)}
    assert stats.reissued >= 1          # the straggler's units were duplicated
    assert stats.completed == 12


# ------------------------------------------------------------- lm data -----


def test_lm_data_deterministic_resume():
    corpus = make_swde_corpus()
    stream = lm_data.corpus_token_stream(corpus)
    a = lm_data.LMBatches(stream, batch=2, seq=8)
    batches = [a.next() for _ in range(5)]
    snap = a.snapshot()
    more_a = [a.next() for _ in range(3)]
    b = lm_data.LMBatches(stream, batch=2, seq=8)
    b.restore(snap)
    more_b = [b.next() for _ in range(3)]
    for x, y in zip(more_a, more_b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


# ------------------------------------------------------------ optimizers ---


@pytest.mark.parametrize("opt", ["adamw", "adafactor", "adam8bit"])
def test_optimizers_reduce_loss(opt, tiny):
    cfg, _ = tiny
    params = init_params(cfg, jax.random.PRNGKey(1))
    init_fn, step = make_train_step(cfg, OptConfig(name=opt, lr=2e-3, warmup_steps=1))
    state = init_fn(params)
    step = jax.jit(step)
    key = jax.random.PRNGKey(2)
    toks = jax.random.randint(key, (4, 17), 0, 64)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    losses = []
    for _ in range(12):
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, (opt, losses[0], losses[-1])
    assert np.isfinite(losses).all()
