"""Compile-only checks for one TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). These tests lower
the main path's device programs at full `qwen2.5-3b` width for one chip of
a `v5e:2x2` topology, so a program the chip's compiler refuses (an
unsupported primitive, a program over HBM) fails here instead of on the
chip:

  * the retrieval ranking `l2_rank_device` over >= 256 rows;
  * the serving engine's paged decode step with bf16 params, whose
    argument + temp bytes must fit the chip's 15.75 GiB of HBM;
  * one chunked-prefill step of the same engine.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.index.vector_index import l2_rank_device
from repro.models import init_params
from repro.serving.engine import ServingEngine

HBM_BYTES = 15.75 * 2 ** 30       # what the compiler reports for one v5e
SLOTS, MAX_LEN = 8, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def engine(one_chip):
    """Full-width engine whose params are shapes on the described chip.
    Its own pool is two slots' worth; the programs are lowered with the
    default pool size for SLOTS."""
    cfg = get_config("qwen2.5-3b")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(init_params, cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                        prefix_cache=True, spec_decode="prompt_lookup",
                        num_pages=2 * (MAX_LEN // 16) + 1)
    num_pages = (SLOTS + 4) * eng.pages_per_slot + 1
    pools = {k: jax.ShapeDtypeStruct((v.shape[0], num_pages) + v.shape[2:],
                                     v.dtype, sharding=one_chip)
             for k, v in eng.alloc.pools.items()}
    return eng, params, pools


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2 ** 30:.2f} GiB of {HBM_BYTES / 2 ** 30} GiB"
    return m


@pytest.mark.parametrize("k", [10, 4096])      # top_k, and the full ranking
def test_retrieval_ranking_compiles(one_chip, k):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    compiled = l2_rank_device.lower(f32(4096, 256), f32(64, 256),
                                    _i32(one_chip), k).compile()
    _fits(compiled)


def test_full_width_paged_decode_fits_one_chip(one_chip, engine):
    eng, params, pools = engine
    assert {str(a.dtype) for a in jax.tree.leaves(params)} == {"bfloat16"}
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng.cache)
    width = eng.pages_per_slot                     # widest decode program
    compiled = eng._paged_decode.lower(
        params, _i32(one_chip, SLOTS, 1), state, pools,
        _i32(one_chip, SLOTS, width), _i32(one_chip, SLOTS)).compile()
    m = _fits(compiled)
    # the params are bf16 already: no cast copy of the tree in temp
    assert m.temp_size_in_bytes < 2 ** 30, m


def test_full_width_chunked_prefill_compiles(one_chip, engine):
    eng, params, pools = engine
    sub = {k: (jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
               if k == "pos" else
               jax.ShapeDtypeStruct(a.shape[:1] + (1,) + a.shape[2:], a.dtype,
                                    sharding=one_chip))
           for k, a in eng.cache.items()}
    cs, ps = eng.chunk_size, eng.page_size
    nb = (cs + ps - 2) // ps + 1
    n_ctx = eng.pages_per_slot
    compiled = eng._chunk_fn(n_ctx, nb, False).lower(
        params, sub, pools, _i32(one_chip, n_ctx), _i32(one_chip, 1, cs),
        _i32(one_chip), _i32(one_chip, nb), _i32(one_chip)).compile()
    _fits(compiled)
