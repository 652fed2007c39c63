"""Compiles the tiled filtered-scan kernel for a described TPU v5e chip.

Nothing runs: the TPU compiler (Mosaic) lowers and compiles the kernel at
the paper's widths — d=768, query tile QB=64, row blocks VB=256 and 128,
k up to 100 — against a chip that is described, not attached.  This
catches what interpret mode cannot: block shapes not aligned to the (8, 128)
tiling, ops with no Mosaic lowering, and kernels that overrun scoped VMEM.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.filtered_scan import filtered_scan_tiled

D, M, F = 768, 10, 2  # paper widths: 768-d rows, 10 int16 attributes
QB = 64
K = 64  # lists
S = 64  # unique probe slots in one batch


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (row store dtype, query dtype, metric, k, Vpad, VB): bf16 lists at two k,
# the SQ8 int8 lists (f32 queries, per-row f32 scales) and l2 with f32 norms
# at the paper cell's padded length and VB 256; and the case-study cell's
# lists (Vpad 46,976 = 367 × 128), which take the 128-row block.
CASES = {
    "bf16-dot-k10": (jnp.bfloat16, jnp.bfloat16, "dot", 10, 36864, 256),
    "bf16-dot-k100": (jnp.bfloat16, jnp.bfloat16, "dot", 100, 36864, 256),
    "bf16-dot-k100-vb128": (jnp.bfloat16, jnp.bfloat16, "dot", 100, 46976,
                            128),
    "int8-dot-k100": (jnp.int8, jnp.float32, "dot", 100, 36864, 256),
    "bf16-l2-k100": (jnp.bfloat16, jnp.bfloat16, "l2", 100, 36864, 256),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tiled_scan_compiles_for_v5e(one_chip, case):
    store, qdtype, metric, k, vpad, vb = CASES[case]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [
        sds((S,), jnp.int32), sds((S,), jnp.int32), sds((QB, D), qdtype),
        sds((QB, F, M), jnp.int16), sds((QB, F, M), jnp.int16),
        sds((K, vpad, D), store), sds((K, vpad, M), jnp.int16),
        sds((K, vpad), jnp.int32),
        sds((K, vpad), jnp.float32) if metric == "l2" else None,
        sds((K, vpad), jnp.float32) if store == jnp.int8 else None,
    ]

    def scan(*a):
        return filtered_scan_tiled(*a, metric=metric, k=k, q_block=QB,
                                   v_block=vb)

    compiled = jax.jit(scan).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # The operands are consumed in place: no relayout copy of the lists or
    # the attribute table (a padded [K, Vpad, M] copy would be ~600 MB).
    assert mem.temp_size_in_bytes < 64 * 2**20, mem
    assert mem.output_size_in_bytes >= S * QB * k * 8
