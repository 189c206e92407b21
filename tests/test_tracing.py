"""Names on the device trace and the host's spans.

Each layer of the FEM step opens a ``jax.named_scope`` ``repro.<layer>``
(PERF.md section 3 lists them); JAX carries the path into the ``op_name``
of every instruction the layer compiles to, which is how a profile of the
chip names the layer of each device operation.  Here the three entries the
benchmark drives are compiled at a small mesh on the CPU: every layer's
scope reaches the compiled program, scopes nest as the calls do, and
nearly all device work carries one.  ``test_chip_compile.py`` checks
that the stream blocks' scopes survive a v5e compile too.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign.runner import CampaignConfig, make_campaign_chunk, run_campaign
from repro.core.stream import broadcast_kset
from repro.fem import backend, meshgen, methods

LAYERS = {
    "repro.fcg_outer", "repro.fcg_inner", "repro.pcg", "repro.ebe_gather",
    "repro.ebe_element", "repro.ebe_scatter", "repro.ebe_diag", "repro.bcsr_spmv",
    "repro.bcsr_gather", "repro.bcsr_scatter", "repro.crs_update",
    "repro.point_strain", "repro.point_force", "repro.multispring",
    "repro.damping", "repro.newmark", "repro.health",
    "repro.stream_block0", "repro.stream_block1",
}
SPANS = {"repro.campaign.chunk", "repro.campaign.fetch", "repro.campaign.bank",
         "repro.campaign.checkpoint"}

_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{\s*$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CONTROL = re.compile(r"(?:body|condition|branch_computations|true_computation|"
                      r"false_computation)=(\{[^}]*\}|%[\w.\-]+)")
FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


def chain(op_name):
    return re.findall(r"repro\.[\w.]+", op_name)


def device_ops(text):
    """``(opcode, op_name)`` of each instruction that runs on the device as
    one operation, as a trace shows it: those of the entry and of loop and
    branch bodies (not of fused computations or reducers), free ones left
    out; ``op_name`` None where it carries no metadata."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif cur is not None and " = " in line:
            cur.append(line)
    control = {entry} | {n for lines in comps.values() for line in lines
                         for ref in _CONTROL.findall(line)
                         for n in re.findall(r"%([\w.\-]+)", ref)}
    out = []
    for c in control:
        for line in comps.get(c, []):
            opc = _OPCODE.search(line.split(" = ", 1)[1])
            if opc and opc.group(1) not in FREE:
                m = re.search(r'op_name="([^"]*)"', line)
                out.append((opc.group(1), m.group(1) if m else None))
    return out


@pytest.fixture(scope="module")
def compiled():
    mesh = meshgen.generate(2, 2, 2, pad_elems_to=8)
    cfg = methods.SeismicConfig(dt=0.01, tol=1e-6, maxiter=50, npart=2, nspring=12,
                                dtype=jnp.float32, warm_start=True, health=True)
    ops = backend.make_operators(mesh, cfg)
    f_t = jnp.zeros(3, jnp.float32)
    out = {}
    for name in ("proposed1", "proposed2"):
        step, _ = methods.make_step(name, ops, offload=True)
        carry = methods.initial_carry(ops, streamed=True, host=True,
                                      ebe=name == "proposed2")
        out[name] = jax.jit(step).lower(carry, f_t).compile().as_text()
    chunk, carry0 = make_campaign_chunk(ops, "proposed2", mesh.surface[:1])
    out["chunk"] = chunk.lower(broadcast_kset(carry0, 2),
                               jnp.zeros((2, 1, 3), jnp.float32)).compile().as_text()
    return out


def test_every_layer_scope_reaches_the_compiled_program(compiled):
    found = {s for text in compiled.values()
             for n in re.findall(r'op_name="([^"]*)"', text) for s in chain(n)}
    assert LAYERS <= found, sorted(LAYERS - found)


def test_scopes_nest_as_the_calls_do(compiled):
    def chains(entry):
        return {tuple(chain(n)) for n in re.findall(r'op_name="([^"]*)"', compiled[entry])}

    ebe = chains("chunk")
    assert ("repro.fcg_outer", "repro.fcg_inner", "repro.ebe_gather") in ebe
    assert ("repro.fcg_outer", "repro.ebe_scatter") in ebe       # the outer product
    assert ("repro.newmark", "repro.ebe_element") in ebe         # the damping product
    assert any(c[:1] == ("repro.health",) for c in ebe)
    crs = chains("proposed1")
    assert ("repro.pcg", "repro.bcsr_spmv", "repro.bcsr_gather") in crs
    assert ("repro.newmark", "repro.bcsr_spmv", "repro.bcsr_scatter") in crs
    assert ("repro.stream_block1", "repro.multispring") in crs
    assert not any("repro.fcg_outer" in c for c in crs)


@pytest.mark.parametrize("entry", ["proposed1", "proposed2", "chunk"])
def test_device_work_is_scoped(compiled, entry):
    named = [n for _, n in device_ops(compiled[entry]) if n]
    scoped = [n for n in named if chain(n)]
    assert len(named) > 100
    assert len(scoped) >= 0.95 * len(named), (
        f"{len(scoped)} of {len(named)} operations scoped; unscoped: "
        f"{sorted({n for n in named if not chain(n)})[:10]}")


def _host_spans(profile_dir):
    path = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return [e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name.startswith("repro.")]


def test_run_campaign_writes_its_host_spans(tmp_path):
    mesh = meshgen.generate(2, 2, 2, pad_elems_to=4)
    cfg = methods.SeismicConfig(dt=0.01, tol=1e-6, maxiter=50, nspring=12,
                                dtype=jnp.float32)
    waves = np.random.default_rng(0).normal(size=(2, 4, 3)).astype(np.float32) * 0.1
    with jax.profiler.trace(str(tmp_path / "prof")):
        res = run_campaign(mesh, cfg, waves, campaign=CampaignConfig(
            kset=2, checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2))
    assert res.completed
    names = _host_spans(str(tmp_path / "prof"))
    assert set(names) == SPANS
    assert names.count("repro.campaign.chunk") == 2              # two chunks of 2 steps
    assert names.count("repro.campaign.bank") == 1               # one round


def test_profile_dir_rides_the_plain_campaign_path_only(tmp_path):
    from repro.launch import campaign

    with pytest.raises(SystemExit, match="--profile-dir rides the plain campaign path"):
        campaign.main(["--scenario", "ricker-soft-basin", "--waves", "1", "--nt", "2",
                       "--mesh-n", "2x2x2", "--profile-dir", str(tmp_path)])
