"""Core HMM invariants: streaming must be semantically transparent."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hetmem
from repro.core.offload import (
    OffloadedAdamWState,
    OffloadConfig,
    offloaded_adamw_apply,
    offloaded_adamw_init,
)
from repro.training.optimizer import AdamWConfig, adamw_apply, adamw_init
from repro.utils.tree import (
    byte_size,
    group_leaves_into_blocks,
    group_like,
    reassemble_blocks,
)


def _params(key, widths=(8, 16, 4, 32, 12)):
    ks = jax.random.split(key, len(widths))
    return {
        f"w{i}": {"kernel": jax.random.normal(k, (w, w)), "bias": jnp.zeros((w,))}
        for i, (k, w) in enumerate(zip(ks, widths))
    }


def test_memory_kinds_present():
    """Every backend of the installed JAX has device + pinned_host memory,
    so Algorithm 3's placements are real copies (CPU included)."""
    kinds = hetmem.supported_memory_kinds()
    assert "device" in kinds and hetmem.HOST in kinds, kinds
    assert hetmem.transfers_supported() and hetmem.host_memory_available()
    assert hetmem.transfer_is_real(hetmem.HOST)
    assert hetmem.transfer_is_real(hetmem.DEVICE)


def test_transfer_to_a_missing_kind_raises():
    """No silent fallback: a placement the runtime cannot make must fail,
    not leave θ where it was."""
    x = jnp.ones(4)
    with pytest.raises(ValueError, match="cannot be a transfer target"):
        hetmem.transfer(x, "no_such_memory")
    with pytest.raises(ValueError, match="cannot be a transfer target"):
        jax.jit(lambda a: hetmem.transfer(a, "unpinned_host"))(x)


@given(npart=st.integers(1, 12), nleaf=st.integers(1, 9))
@settings(max_examples=25, deadline=None)
def test_group_reassemble_roundtrip(npart, nleaf):
    tree = {f"a{i}": np.arange(i + 1, dtype=np.float32) for i in range(nleaf)}
    blocks, spec = group_leaves_into_blocks(tree, npart)
    assert spec.npart == max(1, min(npart, nleaf))
    back = reassemble_blocks(blocks, spec)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(tree)
    for k in tree:
        np.testing.assert_array_equal(back[k], tree[k])


def test_group_like_matches_assignment():
    tree = _params(jax.random.key(0))
    blocks, spec = group_leaves_into_blocks(tree, 3)
    blocks2 = group_like(tree, spec)
    for b1, b2 in zip(blocks, blocks2):
        assert len(b1) == len(b2)
        for x, y in zip(b1, b2):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("npart", [1, 2, 5])
@pytest.mark.parametrize("offload", [True, False])
def test_stream_map_equals_direct(npart, offload):
    tree = _params(jax.random.key(1))
    ps = hetmem.PartitionedState.partition(tree, npart)

    fn = lambda blk: [2.0 * x + 1.0 for x in blk]
    out = hetmem.stream_map(fn, ps, offload=offload).unpartition()
    expect = jax.tree_util.tree_map(lambda x: 2.0 * x + 1.0, tree)
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_stream_map_inside_jit_with_host_state():
    """The Algorithm-3 loop must be jittable with host-resident inputs."""
    tree = {"a": jnp.arange(12.0), "b": jnp.ones((3, 4))}
    ps = hetmem.PartitionedState.partition(tree, 2)
    ps = hetmem.PartitionedState(
        blocks=[hetmem.put_host(b) for b in ps.blocks], spec=ps.spec
    )

    def step_fn(ps, scale):
        return hetmem.stream_map(lambda blk, s: [x * s for x in blk], ps, scale)

    if hetmem.outputs_can_pin_host():  # TPU/GPU: pin outputs in the jit itself
        out_shape = jax.eval_shape(step_fn, ps, jnp.float32(3.0))
        step = jax.jit(step_fn, out_shardings=hetmem.host_out_shardings(out_shape))
        out = step(ps, jnp.float32(3.0))
    else:  # CPU test runtime: eager re-pin after the step
        out = hetmem.repin_state_to_host(jax.jit(step_fn)(ps, jnp.float32(3.0)))
    got = out.unpartition()
    np.testing.assert_allclose(np.asarray(got["a"]), np.arange(12.0) * 3.0)
    # round-trip state should be back in host memory (when the runtime has one)
    if hetmem.host_memory_available():
        for blk in out.blocks:
            for leaf in blk:
                assert leaf.sharding.memory_kind == hetmem.HOST


def test_partition_arrays_roundtrip():
    tree = {"theta": jnp.arange(24.0).reshape(12, 2), "flags": jnp.ones((12,), jnp.int32)}
    parts = hetmem.partition_arrays(tree, 4)
    assert len(parts) == 4
    back = hetmem.concat_blocks(parts)
    np.testing.assert_array_equal(np.asarray(back["theta"]), np.asarray(tree["theta"]))
    with pytest.raises(ValueError):
        hetmem.partition_arrays(tree, 5)


@pytest.mark.parametrize("npart", [1, 3, 7])
def test_offloaded_adamw_matches_resident(npart):
    """Offloaded (streamed, host-resident) AdamW == resident AdamW exactly."""
    cfg = AdamWConfig(learning_rate=1e-2, warmup_steps=1, grad_clip_norm=1.0)
    off = OffloadConfig(optimizer_state=True, optimizer_npart=npart)
    params = _params(jax.random.key(2))
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.key(3), p.shape), params
    )

    st_res = adamw_init(params, cfg)
    st_off = offloaded_adamw_init(params, cfg, off)

    p_res, st_res = adamw_apply(grads, params, st_res, cfg)
    p_off, st_off = offloaded_adamw_apply(grads, params, st_off, cfg)
    st_off = OffloadedAdamWState(
        step=st_off.step, moments=hetmem.repin_state_to_host(st_off.moments)
    )
    p_res, st_res = adamw_apply(grads, p_res, st_res, cfg)
    p_off, st_off = offloaded_adamw_apply(grads, p_off, st_off, cfg)

    for a, b in zip(jax.tree_util.tree_leaves(p_res), jax.tree_util.tree_leaves(p_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0)


def test_offloaded_adamw_jitted():
    cfg = AdamWConfig(learning_rate=1e-2, warmup_steps=1)
    off = OffloadConfig(optimizer_state=True, optimizer_npart=3)
    params = _params(jax.random.key(4), widths=(6, 10))
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    state = offloaded_adamw_init(params, cfg, off)

    step = jax.jit(lambda g, p, s: offloaded_adamw_apply(g, p, s, cfg))
    p1, s1 = step(grads, params, state)
    p2, _ = step(grads, p1, s1)
    assert np.isfinite(np.asarray(jax.tree_util.tree_leaves(p2)[0])).all()


def test_pipeline_cost_model_matches_paper():
    """Paper §2.3: 0.33 s compute vs 0.38 s transfer/step → pipelined ≈ 0.38 s."""
    from repro.core.pipeline import breakeven_link_gbps, pipeline_time

    npart = 78  # 7.8M elements / 0.1M per block
    # paper totals per time step: compute 0.33 s, transfer 0.38 s (in+out)
    per_block_compute = 0.33 / npart
    theta_bytes = 7.781e6 * 24e3  # 24 KB/element
    per_block_bytes = theta_bytes / npart
    cost = pipeline_time(
        compute_s_per_block=per_block_compute,
        bytes_in_per_block=per_block_bytes,
        bytes_out_per_block=per_block_bytes,
        link_gbps=900.0,
        npart=npart,
    )
    assert cost.bound == "compute" or cost.pipelined_s < cost.serial_s
    # Overlap must hide the smaller of compute/transfer:
    assert cost.pipelined_s <= 0.33 + 0.38  # ≤ unpipelined
    assert cost.pipelined_s >= max(0.33, per_block_bytes / 900e9 * npart) * 0.9
    # PCIe Gen5 x16 (~63 GB/s) should be transfer-bound — the paper's claim.
    cost_pcie = pipeline_time(
        compute_s_per_block=per_block_compute,
        bytes_in_per_block=per_block_bytes,
        bytes_out_per_block=per_block_bytes,
        link_gbps=63.0,
        npart=npart,
    )
    assert cost_pcie.bound == "transfer"
    assert cost_pcie.pipelined_s > cost.pipelined_s
    be = breakeven_link_gbps(
        compute_s_per_block=per_block_compute, bytes_per_block=per_block_bytes
    )
    assert 63.0 < be < 900.0


def test_byte_size():
    assert byte_size({"a": jnp.zeros((4, 4), jnp.float32)}) == 64
