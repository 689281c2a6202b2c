"""Compile the serving hot path for a described TPU v5e (no chip needed).

The TPU compiler ships with jaxlib: it compiles for a v5e that is
described, not attached, and refuses what the chip would refuse —
unaligned kernel tiles, VMEM overuse, programs that do not fit HBM.
Interpret-mode tests cannot see any of that.  Nothing here runs, so
nothing here is a time.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as da
from repro.kernels import ops
from repro.models.api import build_model

B, H, DH, P, PAGES_PER_SLOT = 4, 32, 64, 64, 8
N_PAGES = B * PAGES_PER_SLOT
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(monkeypatch):
    """Compile for the chip: kernels lower through Mosaic (the CPU
    default would pick interpret mode), and the persistent cache is off
    (an entry written without a chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


KERNELS = {
    "paged_bf16": (da.decode_attention_paged_resident, lambda s: (
        s((B, H, DH), "bfloat16"), s((N_PAGES, H, P, DH), "bfloat16"),
        s((N_PAGES, H, P, DH), "bfloat16"), s((B,), "int32"),
        s((B, PAGES_PER_SLOT), "int32"), s((H,), "int32"))),
    "paged_int8": (da.decode_attention_int8_paged_resident, lambda s: (
        s((B, H, DH), "bfloat16"), s((N_PAGES, H, P, DH), "int8"),
        s((N_PAGES, H, P, 1), "float32"), s((N_PAGES, H, P, DH), "int8"),
        s((N_PAGES, H, P, 1), "float32"), s((B,), "int32"),
        s((B, PAGES_PER_SLOT), "int32"), s((H,), "int32"))),
    "dense_bf16": (da.decode_attention_resident, lambda s: (
        s((B, H, DH), "bfloat16"), s((B, H, P * PAGES_PER_SLOT, DH),
                                     "bfloat16"),
        s((B, H, P * PAGES_PER_SLOT, DH), "bfloat16"), s((B,), "int32"),
        s((H,), "int32"))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_resident_decode_kernel_compiles_for_v5e(name, one_chip,
                                                 tpu_compile):
    fn, args = KERNELS[name]
    spec = lambda shape, dtype: _spec(shape, dtype, one_chip)
    compiled = fn.lower(*args(spec)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_musicgen_large_paged_decode_step_compiles_for_v5e(one_chip,
                                                           tpu_compile):
    """The served decode step at published widths (48 layers, d_model
    2048, 32 heads of 64, bf16) with the Pallas paged kernel: it must
    hold the kernel and fit one chip's HBM."""
    cfg = get_config("musicgen-large")
    model = build_model(cfg, use_kernel=True)
    place = lambda t: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), t)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda p: model.init_paged_state(p, B, N_PAGES, P, PAGES_PER_SLOT),
        params)
    rows = jax.ShapeDtypeStruct((cfg.n_layers, H), jnp.int32)
    state = dict(state, head_rows=rows, head_inv=rows)
    tokens = _spec((B,), "int32", one_chip)
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    compiled = step.lower(place(params), place(state), tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used
