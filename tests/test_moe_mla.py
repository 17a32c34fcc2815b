"""The expert layer as one device of an expert-parallel deployment, YaRN
latent attention, and the engine's expert-layer counters (CPU, test sizes).

- The held-expert layer's shares add up to the uncut layer: four devices
  holding experts 0-15, 16-31, 32-47 and 48-63 of 64, with the shared
  experts counted once, give a plain per-token loop over all 64 experts.
- No token is dropped: a token's output is the same alone, in a 32-token
  chunk (also one of 32 copies of itself, which a capacity-bound layer
  would drop), in a verify batch and in a training-sized call.
- Up to `MOE_DENSE_MAX_TOKENS` tokens the layer runs every held expert on
  every token; above it, the picks sorted by expert through
  `lax.ragged_dot`. Both forms give the same outputs and gradients.
- The YaRN inverse frequencies and softmax scale are the published
  formula's (DeepSeek-V2 `modeling_deepseek.py`), 1.5896 at factor 40.
- `held_assignments` and `routed_assignments` equal a host recount.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params, init_decode_cache, prefill_chunk
from repro.models import layers as L
from repro.serving.engine import Request, ServingEngine

DSV2 = "deepseek-v2-lite-16b"


def _layer_cfg():
    """The smoke config at the published router: 64 experts, top 6."""
    return get_smoke_config(DSV2).replace(n_experts=64, moe_top_k=6,
                                          n_shared_experts=2)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _uncut_loop(cfg, p, x):
    """The whole layer, token by token, expert by expert (numpy, float64)."""
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    w = {k: np.asarray(v, np.float64) for k, v in p.items() if k != "shared"}
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    out = np.zeros_like(x)
    for t, xt in enumerate(x):
        logits = xt @ w["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        for e in np.argsort(-probs, kind="stable")[:cfg.moe_top_k]:
            h = _silu(xt @ w["w_gate"][e]) * (xt @ w["w_up"][e])
            out[t] += probs[e] * cfg.routed_scaling_factor * (h @ w["w_down"][e])
        out[t] += (_silu(xt @ sh["w_gate"]) * (xt @ sh["w_up"])) @ sh["w_down"]
    return out


# (B, S) of a call on each side of MOE_DENSE_MAX_TOKENS
FORMS = {"dense": (2, 8), "sorted": (2, L.MOE_DENSE_MAX_TOKENS // 2 + 8)}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_held_shares_add_up_to_the_uncut_layer(form):
    cfg = _layer_cfg()
    p = L.moe_init(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), FORMS[form] + (cfg.d_model,))
    shared = p["shared"]
    h = jax.nn.silu(x @ shared["w_gate"]) * (x @ shared["w_up"])
    shared_out = h @ shared["w_down"]
    total, held = 0.0, 0
    for k in range(4):
        part = cfg.replace(n_held_experts=16, held_expert_start=16 * k)
        pk = dict(p, **{n: p[n][16 * k:16 * (k + 1)]
                        for n in ("w_gate", "w_up", "w_down")})
        y, h_k, _ = L.moe_apply(part, pk, x)
        total = total + y
        held = held + h_k
    total = total - 3 * shared_out          # every share adds the shared part
    want = _uncut_loop(cfg, p, x).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total), want, rtol=1e-4, atol=1e-5)
    whole, h_all, _ = L.moe_apply(cfg, p, x)
    np.testing.assert_allclose(np.asarray(whole), want, rtol=1e-4, atol=1e-5)
    assert (np.asarray(held) == cfg.moe_top_k).all()
    assert (np.asarray(h_all) == cfg.moe_top_k).all()


def test_the_form_follows_the_token_count():
    """The sorted form's grouped matmuls appear above the threshold only."""
    cfg = _layer_cfg()
    p = L.moe_init(cfg, jax.random.PRNGKey(1))
    n = L.MOE_DENSE_MAX_TOKENS

    def uses_ragged(shape):
        x = jax.ShapeDtypeStruct(shape + (cfg.d_model,), jnp.float32)
        return "ragged_dot" in str(jax.make_jaxpr(
            lambda x: L.moe_apply(cfg, p, x))(x))
    assert not uses_ragged((1, n)) and not uses_ragged((32, n // 32))
    assert uses_ragged((1, n + 1)) and uses_ragged((n // 32 + 1, 32))


def test_both_forms_give_the_same_gradients():
    """A training step's gradients through the sorted form equal the dense
    form's, for the inputs and every weight (all 64 experts held)."""
    cfg = _layer_cfg()
    p = L.moe_init(cfg, jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 96, cfg.d_model))

    def loss(p, x):          # a sum over tokens (the aux loss is a mean)
        return jnp.sum(jnp.sin(L.moe_apply(cfg, p, x)[0]))
    g_sorted = jax.grad(loss, argnums=(0, 1))(p, x)     # 288 tokens a call
    g_dense = [jax.grad(loss, argnums=(0, 1))(p, x[i:i + 1]) for i in range(3)]
    np.testing.assert_allclose(
        g_sorted[1], jnp.concatenate([g[1] for g in g_dense]), rtol=1e-4,
        atol=1e-6)
    for name in ("router", "w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(g_sorted[0][name],
                                   sum(g[0][name] for g in g_dense),
                                   rtol=1e-4, atol=1e-6)


def test_a_share_drops_no_token():
    """A token's output does not depend on what shares its call."""
    cfg = _layer_cfg().replace(n_held_experts=16, held_expert_start=16)
    p = jax.tree.map(lambda a: a[:16] if a.ndim == 3 and a.shape[0] == 64
                     else a, L.moe_init(_layer_cfg(), jax.random.PRNGKey(3)))
    xs = jax.random.normal(jax.random.PRNGKey(4), (32, cfg.d_model))
    fn = jax.jit(lambda x: L.moe_apply(cfg, p, x)[:2])
    alone = [fn(xs[t][None, None]) for t in range(32)]
    chunk = fn(xs[None])                          # one 32-token chunk
    same = fn(jnp.broadcast_to(xs[0], (1, 32, cfg.d_model)))  # 32 copies
    verify = fn(xs.reshape(8, 4, cfg.d_model))    # 8 rows of 4 positions
    # a training-sized call, past MOE_DENSE_MAX_TOKENS: the sorted form
    big = fn(jnp.tile(xs[None], (L.MOE_DENSE_MAX_TOKENS // 32 + 1, 1, 1)))
    for t in range(32):
        y, h = alone[t]
        np.testing.assert_allclose(chunk[0][0, t], y[0, 0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(verify[0][t // 4, t % 4], y[0, 0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(big[0][:, t], jnp.broadcast_to(
            y[0, 0], big[0][:, t].shape), rtol=1e-5, atol=1e-6)
        assert chunk[1][0, t] == verify[1][t // 4, t % 4] == h[0, 0]
        assert (big[1][:, t] == h[0, 0]).all()
    np.testing.assert_allclose(same[0][0], jnp.broadcast_to(alone[0][0][0, 0],
                                                            (32, cfg.d_model)),
                               rtol=1e-5, atol=1e-6)


def test_gate_as_published_no_renormalisation():
    """DeepSeek-V2-Lite gates with the raw top-6 softmax probabilities."""
    cfg = _layer_cfg()
    assert not get_smoke_config(DSV2).norm_topk_prob
    router = jax.random.normal(jax.random.PRNGKey(5), (cfg.d_model, 64))
    x = jax.random.normal(jax.random.PRNGKey(6), (3, cfg.d_model))
    probs, top_w, top_i = L.moe_route(cfg, router, x)
    np.testing.assert_allclose(
        top_w, jnp.take_along_axis(probs, top_i, -1), rtol=1e-6)
    assert float(top_w.sum(-1).max()) < 1.0
    _, w_norm, _ = L.moe_route(cfg.replace(norm_topk_prob=True), router, x)
    np.testing.assert_allclose(w_norm.sum(-1), 1.0, rtol=1e-6)


def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
                    mscale_all_dim):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies and cos/sin
    scale, transcribed from modeling_deepseek.py."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = freq_inter * (1 - mask) + freq_extra * mask

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0
    return inv, get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim), \
        (low, high)


def test_yarn_as_published():
    from repro.configs import get_config
    cfg = get_config(DSV2)
    y = cfg.yarn
    assert y["factor"] == 40 and y["original_max_position_embeddings"] == 4096
    want, scale, bounds = _published_yarn(64, 10000.0, 40, 4096, 32, 1,
                                          0.707, 0.707)
    assert bounds == (10, 23)
    got, got_scale = L.yarn_freqs(64, cfg.rope_theta, y)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    assert got_scale == scale == 1.0
    # the softmax scale gains mscale^2 = (0.1 * 0.707 * ln 40 + 1)^2
    assert L.yarn_mscale(40, 0.707) == pytest.approx(1.26081, abs=1e-5)
    ratio = L.mla_softmax_scale(cfg) / (128 + 64) ** -0.5
    assert ratio == pytest.approx(1.5896, abs=1e-4)
    # rope on positions: the angle of pair i at position t is t * inv_freq[i]
    x = jnp.ones((1, 3, 1, 64))
    out = L.apply_rope(x, jnp.arange(3)[None], cfg.rope_theta, y)
    ang = 2 * want
    np.testing.assert_allclose(np.asarray(out[0, 2, 0, :32]),
                               np.cos(ang) - np.sin(ang), rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ counters ---


def _engine_cfg(**kw):
    return get_smoke_config(DSV2).replace(n_experts=8, n_held_experts=4,
                                          held_expert_start=4, **kw)


def _run(cfg, params, prompts, spec):
    eng = ServingEngine(cfg, params, slots=2, max_len=64, page_size=8,
                        chunk_size=5, spec_decode=spec)
    eng.submit_many([Request(rid=i, prompt=p, max_new=6, eos_id=-1)
                     for i, p in enumerate(prompts)])
    return eng, eng.run()


PROMPTS = [[7, 3, 9, 4, 2, 8, 1, 6, 5, 7, 3, 2, 10 + i, 20 + i, 7, 3, 9]
           for i in range(3)]


def test_held_assignments_equal_a_host_recount():
    """Plain decode: every position of prompt + fed-back tokens passes the
    expert layers once, so the counters equal a one-call recount of each
    request's sequence."""
    cfg = _engine_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng, done = _run(cfg, params, PROMPTS, "off")
    held = routed = 0
    for i, p in enumerate(PROMPTS):
        seq = p + done[i].out[:-1]
        cache = init_decode_cache(cfg, 1, 64)
        _, new = prefill_chunk(cfg, params,
                               {"tokens": jnp.asarray([seq], jnp.int32)},
                               cache, count_held=True)
        held += int(new["moe_held"].sum())
        routed += len(seq) * (cfg.num_layers - cfg.first_dense_layers) \
            * cfg.moe_top_k
    assert eng.stats["routed_assignments"] == routed
    assert eng.stats["held_assignments"] == held
    assert 0 < held < routed


def test_verify_rounds_count_real_tokens_only():
    """Speculative rounds: with every expert held, each real token's every
    pick is held, so the counters agree exactly; padding (free rows, unused
    draft positions) would count picks no real token made."""
    cfg = _engine_cfg().replace(n_held_experts=0, held_expert_start=0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng, _ = _run(cfg, params, PROMPTS, "prompt_lookup")
    assert eng.stats["spec_rounds"] > 0
    assert eng.stats["held_assignments"] == eng.stats["routed_assignments"] > 0
    dense = get_smoke_config("qwen2.5-3b")
    eng, _ = _run(dense, init_params(dense, jax.random.PRNGKey(0)), PROMPTS,
                  "prompt_lookup")
    assert eng.stats["held_assignments"] == eng.stats["routed_assignments"] == 0
