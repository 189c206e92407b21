"""The main-path kernels compiled for a described TPU v5e at real widths.

The TPU compiler ships with JAX and compiles for a chip that is described,
not attached, so these run anywhere and cost no chip time.  They catch what
interpret mode cannot: Mosaic's tiling and VMEM rules, and whether a call
fits one chip's 16 GB of HBM.  Nothing here runs a kernel.

Widths are those of the real-size campaign: 20×20×20 hexes = 48,000 tet10
elements (192,000 evaluation points) at the paper's 150 springs per point,
fp32, each kernel once alone and once vmapped over a k-set of 2.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fem import multispring as ms, quadrature as quad
from repro.kernels.ebe_matvec import ebe_element_lanes_pallas
from repro.kernels.multispring import multispring_pallas

HBM_BYTES = 16e9  # one v5e chip
E = 48_000
P = E * quad.NPOINT
S = ms.NSPRING_DEFAULT


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_on_v5e(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one v5e"


@pytest.mark.parametrize("kset", [1, 2])
def test_ebe_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kset):
    def sds(*shape):
        lead = (kset,) if kset > 1 else ()
        return jax.ShapeDtypeStruct(lead + shape, jnp.float32, sharding=one_chip)

    args = (sds(quad.NDOF, E), sds(E, quad.NPOINT, 6, 6), sds(E, 3, 3),
            sds(E, quad.NPOINT), sds(E))
    fn = lambda u, D, J, w, c: ebe_element_lanes_pallas(  # noqa: E731
        u, D, J, w, c, tile_e=512, interpret=False)
    _compile_on_v5e(jax.vmap(fn) if kset > 1 else fn, *args)


@pytest.mark.parametrize("kset", [1, 2])
def test_multispring_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kset):
    lead = (kset,) if kset > 1 else ()

    def sds(shape, dtype=jnp.float32, batched=True):
        return jax.ShapeDtypeStruct((lead if batched else ()) + shape, dtype,
                                    sharding=one_chip)

    state = {k: sds((P, S), jnp.int32 if k in ("direction", "virgin") else jnp.float32)
             for k in ("gamma_rev", "tau_rev", "gamma_prev", "gamma_max",
                       "direction", "virgin")}
    params = ms.SpringParams(*(sds((P,)) for _ in range(4)))
    fn = lambda e, st, p, n, w: multispring_pallas(  # noqa: E731
        e, st, p, n, w, tile_p=256, interpret=False)
    if kset > 1:
        fn = jax.vmap(fn, in_axes=(0, 0, 0, None, None))
    _compile_on_v5e(fn, sds((P, 6)), state, params,
                    sds((S, 6), batched=False), sds((S,), batched=False))
