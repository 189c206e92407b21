"""The scope rules (``harness/scopes.py``) and the readers built on them, on
hand-made traces whose answers are known, on a profile made here on the
CPU, and on small scoped traces recorded on a TPU v5e."""
import glob
import importlib
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import scopes as S  # noqa: E402
from harness import trace as T  # noqa: E402

import record  # noqa: E402

OUTER = "jit(chunk)/while/body/closed_call/vmap()/repro.fcg_outer/while/body"
INNER = OUTER + "/repro.fcg_inner/while/body"
# window [0, 1000] ns: an inner gather and scatter that overlap, an outer
# vector op, an unscoped op, an op the map does not know, one outside
HAND = T.Trace(
    ops=[["%fusion.1 = f32[2,30,8] fusion(f32[2,8] %x), kind=kLoop", 100, 200],
         ["%fusion.2 = f32[2,8] fusion(f32[2,30,8] %y), kind=kLoop", 250, 150],
         ["%add.3 = f32[2,8] add(f32[2,8] %a, f32[2,8] %b)", 500, 100],
         ["%copy.4 = f32[2,8] copy(f32[8,2] %c)", 650, 50],
         ["%copy-start.5 = (f32[8], f32[8]) copy-start(f32[8]{0:S(5)} %c)", 800, 20],
         ["%fusion.1 = f32[2,30,8] fusion(f32[2,8] %x), kind=kLoop", 1200, 100]],
    spans=[["bench.window", 0, 1000], ["bench.call", 0, 1000]],
    device="/device:TPU:0", lines={"XLA Ops": 6})
HAND_SCOPES = {"fusion.1": INNER + "/closed_call/repro.ebe_gather/gather",
               "fusion.2": INNER + "/vmap(repro.ebe_scatter)/scatter-add",
               "add.3": OUTER + "/add",
               "copy.4": "jit(chunk)/while/body/transpose"}


def ctx_for(tr, scopes, **kw):
    c = dict(trace=tr, scopes=scopes, case_steps=2, steps=1, cases=2,
             iters=[10, 14], notes={})
    c.update(kw)
    return types.SimpleNamespace(**c)


def read(name, ctx):
    return importlib.import_module(f"metrics.{name}").read(ctx)


def test_chain_and_innermost_scope():
    path = HAND_SCOPES["fusion.1"]
    assert S.chain(path) == ["repro.fcg_outer", "repro.fcg_inner", "repro.ebe_gather"]
    assert S.innermost(path) == "repro.ebe_gather"
    assert S.innermost(HAND_SCOPES["fusion.2"]) == "repro.ebe_scatter"  # vmap(…) wraps it
    assert S.innermost("jit(step)/repro.stream_block3/repro.multispring/eq") == "repro.multispring"
    assert S.innermost("jit(chunk)/while/body/add") is None and S.chain("") == []
    assert S.instr(HAND.ops[0][0]) == "fusion.1" and S.instr("fusion.7") == "fusion.7"


def test_time_under_a_scope_is_the_union_of_its_nested_ops_in_the_window():
    c = ctx_for(HAND, HAND_SCOPES)
    assert S.ns_under(c, "repro.ebe_gather") == 200
    assert S.ns_under(c, "repro.fcg_inner") == 300          # [100, 400]: overlap once
    assert S.ns_under(c, "repro.fcg_outer") == 400          # and the outer add
    assert S.ms_per_step(c, "repro.fcg_inner") == pytest.approx(300e-6 / 2)
    assert S.ms_per_step(c, "repro.crs_update") is None     # nothing ran under it
    assert S.table(c) == pytest.approx({
        "repro.ebe_gather": 100e-6, "repro.ebe_scatter": 75e-6,
        "repro.fcg_outer": 50e-6, "": 35e-6})                # the copy and the unknown op


def test_scope_readers():
    c = ctx_for(HAND, HAND_SCOPES)
    assert read("fcg_inner_ms_per_step", c) == pytest.approx(150e-6)
    assert read("ebe_gather_ms_per_step", c) == pytest.approx(100e-6)
    assert read("ebe_scatter_ms_per_step", c) == pytest.approx(75e-6)
    assert read("bcsr_spmv_ms_per_step", c) is None
    assert read("crs_update_ms_per_step", c) is None
    assert read("unscoped_ms_per_step", c) == pytest.approx(35e-6)
    assert c.notes["scopes_ms_per_step"]["(unscoped)"] == pytest.approx(35e-6)
    bcsr = {"fusion.1": "jit(step)/repro.pcg/while/body/repro.bcsr_spmv/repro.bcsr_gather/gather",
            "fusion.2": "jit(step)/repro.newmark/repro.bcsr_spmv/repro.bcsr_scatter/scatter-add",
            "add.3": "jit(step)/repro.crs_update/add"}
    c = ctx_for(HAND, bcsr, case_steps=1)
    assert read("bcsr_spmv_ms_per_step", c) == pytest.approx(300e-6)
    assert read("crs_update_ms_per_step", c) == pytest.approx(100e-6)


def test_a_program_without_scopes_reads_nothing():
    """The parent of the scopes (or a recorded trace without a map): every
    scope reader returns None, and does not raise."""
    for m in ({}, {"fusion.1": "jit(chunk)/while/body/gather"}):
        c = ctx_for(HAND, m)
        for name in ("fcg_inner_ms_per_step", "ebe_gather_ms_per_step",
                     "ebe_scatter_ms_per_step", "bcsr_spmv_ms_per_step",
                     "crs_update_ms_per_step", "unscoped_ms_per_step"):
            assert read(name, c) is None, name


@pytest.mark.parametrize("iters,cases,want", [
    ([12, 12, 9, 9], 2, 0.0),          # equal members: no lockstep loss
    ([10, 14], 2, 100 * 4 / 28),       # 14.3%
    ([10, 14, 7, 7, 3], 2, 100 * 4 / 42),  # a trailing partial call is left out
    ([11, 13, 9], 1, 0.0),             # one member per call
])
def test_fcg_lockstep_share(iters, cases, want):
    c = ctx_for(HAND, {}, iters=iters, cases=cases)
    assert read("fcg_lockstep_share", c) == pytest.approx(want)
    assert read("fcg_lockstep_share", ctx_for(HAND, {}, iters=[])) is None


def _cpu_profile(dirname, inner="repro.ebe_gather"):
    """A profile of a small vmapped loop under a ``bench.window`` span, and
    the trace of its host spans as ``bench/run.py`` would read them."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("repro.fcg_outer"):
            def body(i, c):
                with jax.named_scope(inner):
                    return c + jnp.cos(c)
            return jax.lax.fori_loop(0, 3, body, jnp.sin(x) * 2)

    g = jax.jit(jax.vmap(f))
    x = jnp.ones((2, 64))
    g(x).block_until_ready()
    with jax.profiler.trace(dirname):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            g(x).block_until_ready()
    path = glob.glob(os.path.join(dirname, "**", "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = [[e.name, e.start_ns, e.duration_ns] for p in pd.planes
             if p.name.startswith("/host:") for line in p.lines for e in line.events
             if e.name.startswith(T.SPAN_PREFIX)]
    return path, T.Trace(ops=[], spans=spans)


def test_map_from_a_profile_and_the_run_finds_its_own(tmp_path, monkeypatch):
    """Where the device's events carry no op name (the CPU), the map comes
    from the programs' modules that the profile stores.  A run's readers
    find the profile ``bench/run.py`` is tracing into (``bench_trace_*`` in
    the temporary directory) by its window span: a newer profile of another
    run is passed over, and a run whose profile is not there reads nothing."""
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    path, tr = _cpu_profile(str(tmp_path / "bench_trace_x"))
    m = S.from_xplane(path, device_index=0)
    assert {S.innermost(v) for v in m.values()} >= {"repro.fcg_outer", "repro.ebe_gather"}
    (start, dur), = S.span_times(path, T.WINDOW_SPAN)
    assert (start, start + dur) == tr.window
    _, other = _cpu_profile(str(tmp_path / "bench_trace_y"), inner="repro.ebe_scatter")
    c = types.SimpleNamespace(trace=tr)
    assert S.of(c) == m and c.scopes == m
    c = types.SimpleNamespace(trace=other)
    assert {S.innermost(v) for v in S.of(c).values()} >= {"repro.ebe_scatter"}
    lo, hi = tr.window
    c = types.SimpleNamespace(trace=T.Trace(ops=[], spans=[[T.WINDOW_SPAN, lo + 5, hi - lo]]))
    assert S.of(c) == {} and read("unscoped_ms_per_step", ctx_for(HAND, S.of(c))) is None


def test_map_from_a_tpu_profile():
    """A profile taken on a TPU v5e of a small vmapped loop with two scopes
    (``repro.a`` around the body's prologue, ``repro.b`` in a
    ``fori_loop``): the device's op kinds carry their ``op_name`` as the
    ``tf_op`` stat, which the map takes first."""
    m = S.from_xplane(os.path.join(BENCH, "data", "profile_tpu_v5e_scoped.xplane.pb"))
    assert {k: S.innermost(v) for k, v in m.items()} == {
        "slice.4": "repro.b", "sine_multiply_fusion": "repro.a", "fusion.5": "repro.b"}


def test_strip_kernel_locations():
    """Two kernels that differ only in where they were traced from (the
    file, line and scopes in their serialized MLIR) compare equal."""
    import base64
    import io

    from jax._src.lib.mlir import ir

    def line(loc, op="test.op"):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            m = ir.Module.parse('"%s"() : () -> () loc("%s":3:1)' % (op, loc))
            buf = io.BytesIO()
            m.operation.write_bytecode(buf)
        return ('%%k = f32[8] custom-call(), backend_config={"custom_call_config":'
                '{"body":"%s"}}' % base64.b64encode(buf.getvalue()).decode())

    a, b = line("a.py"), line("/elsewhere/repro.fcg_inner/b.py")
    assert a != b
    assert record.strip_kernel_locations(a) == record.strip_kernel_locations(b)
    assert record.strip_kernel_locations(line("a.py", "test.other")) != record.strip_kernel_locations(a)


def test_strip_metadata_and_canonical_names():
    hlo = ('HloModule m\n\nFileNames\n1 "a.py"\n\nStackFrames\n1 1 1\n\n'
           'ENTRY %main.3 (p.1: f32[2]) -> f32[2] {\n'
           '  %p.1 = f32[2] parameter(0), metadata={op_name="x"}\n'
           '  ROOT %add.7 = f32[2] add(%p.1, %p.1), metadata={op_name="jit(f)/repro.a{b}/add" '
           'source_file="q\\"}.py" source_line=3}\n}\n')
    out = record.strip_metadata(hlo)
    assert "metadata" not in out and "FileNames" not in out and "StackFrames" not in out
    assert "ROOT %add.7 = f32[2] add(%p.1, %p.1)\n}" in out
    renamed = out.replace("add.7", "add.9").replace("main.3", "main.5")
    assert record.canonical_names(renamed) == record.canonical_names(out) != out


@pytest.mark.parametrize("at,inside", [("boundary", 1500), ("middle", 2750)])
def test_recording_keeps_a_stretch_around_the_second_call_that_fits(at, inside):
    ops = [[f"%fusion.{i} = f32[8] fusion(f32[8] %x)", 10.0 * i, 8.0] for i in range(400)]
    tr = T.Trace(ops=ops, spans=[["bench.window", 0, 4000], ["bench.call", 0, 1500],
                                 ["bench.call", 1500, 2500]],
                 device="/device:TPU:0", lines={"XLA Ops": 400})
    m = {f"fusion.{i}": "jit(f)/repro.pcg/add" for i in range(0, 400, 2)}
    text = record.trimmed(tr, 8000, m, at)
    part, kept = S.load(text)
    lo, hi = part.window
    assert len(text) <= 8000 and lo < inside < hi and 0 < hi - lo < 4000
    assert at == "boundary" or 1500 < lo
    assert {S.instr(o[0]) for o in part.ops} >= set(kept) and len(kept) < len(m)
    c = ctx_for(part, kept, case_steps=1)
    assert S.ns_under(c, "repro.pcg") == pytest.approx(
        sum(d for n, s, d in part.ops if S.instr(n) in kept))


def _pb(*fields):
    """Protobuf wire bytes of ``(field, value)`` pairs: an int as a varint,
    bytes or str as length-delimited."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_map_from_a_module_and_merged_op_names():
    """The module map holds the instructions that carry an ``op_name``; a
    fusion the compiler made without one stays unscoped.  Where XLA merged
    operations it joins their paths with ``;``: the first names the op."""
    path = "jit(chunk)/while/body/vmap(repro.fcg_outer)/repro.ebe_element/slice"
    fused = _pb((2, _pb((1, "slice.9"), (7, _pb((2, path))))),
                (2, _pb((1, "dynamic-update-slice.4"))))
    entry = _pb((2, _pb((1, "constant_dynamic-update-slice_fusion.7"))),
                (2, _pb((1, "copy.3"), (7, _pb((1, "copy"))))))
    assert S.hlo_op_names(_pb((1, _pb((3, fused), (3, entry))))) == {"slice.9": path}
    merged = path + ";vmap(repro.fcg_outer)/repro.ebe_scatter/squeeze"
    assert S.chain(merged) == ["repro.fcg_outer", "repro.ebe_element"]


RECORDED = {  # cell → (file, case-steps a call, config)
    "ebe-k2-resident": ("trace_tpu_v5e_scoped_ebe.json", 2,
                        {"n_elem": 63888, "n_nodes": 91125, "nspring": 150}),
    "crs-hoststream": ("trace_tpu_v5e_scoped_hoststream.json", 1,
                       {"n_elem": 24576, "n_nodes": 35937, "nspring": 150}),
    "crs-resident": ("trace_tpu_v5e_scoped_resident.json", 1,
                     {"n_elem": 24576, "n_nodes": 35937, "nspring": 150}),
}
# each cell's readings on its recording (ms per case-step; None: not in the cell)
PINNED = {
    "ebe-k2-resident": {"fcg_inner_ms_per_step": 0.0014255, "ebe_gather_ms_per_step": 4.9884585,
                        "ebe_scatter_ms_per_step": 28.391789, "bcsr_spmv_ms_per_step": None,
                        "crs_update_ms_per_step": None, "unscoped_ms_per_step": 8.409394},
    "crs-hoststream": {"fcg_inner_ms_per_step": None, "ebe_gather_ms_per_step": None,
                       "ebe_scatter_ms_per_step": None, "bcsr_spmv_ms_per_step": 227.09001555,
                       "crs_update_ms_per_step": 341.129349, "unscoped_ms_per_step": 37.431012},
    "crs-resident": {"fcg_inner_ms_per_step": None, "ebe_gather_ms_per_step": None,
                     "ebe_scatter_ms_per_step": None, "bcsr_spmv_ms_per_step": 512.57792678,
                     "crs_update_ms_per_step": 341.043264, "unscoped_ms_per_step": 3.42406539},
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_scoped_tpu_trace(cell):
    """A stretch of a traced window on a TPU v5e around the start of a call
    (the end of one step and the start of the next), with the scope map
    of its operations: each reader's value is pinned; the innermost scopes
    and the unscoped ops split the busy time; the layers the step names
    fit inside the glue; in the host-streamed cell the unscoped time is the
    device's waits on the host link (the compiler's copies carry no scope)."""
    name, case_steps, config = RECORDED[cell]
    with open(os.path.join(BENCH, "data", name)) as f:
        tr, m = S.load(f.read())
    assert not [o for o in tr.ops if T.opcode(o[0]) in T.CONTAINERS]
    c = ctx_for(tr, m, case_steps=case_steps, config=config)
    for metric, want in PINNED[cell].items():
        got = read(metric, c)
        assert (got is None) if want is None else got == pytest.approx(want, rel=1e-6), metric
    busy, _ = T.busy_idle(tr)
    assert sum(S.table(c).values()) * case_steps == pytest.approx(busy * 1e3)
    glue = read("glue_ms_per_step", c)
    named = [read(k, c) or 0.0 for k in ("ebe_gather_ms_per_step", "ebe_scatter_ms_per_step",
                                         "bcsr_spmv_ms_per_step", "crs_update_ms_per_step")]
    assert sum(named) <= glue
    if cell == "crs-hoststream":
        wait = read("host_link_wait_ms_per_step", c)
        assert wait == pytest.approx(35.405861, rel=1e-6)
        assert 0 < read("unscoped_ms_per_step", c) - wait < 0.06 * wait
    assert {"repro.fcg_inner", "repro.pcg"} & {s for v in m.values() for s in S.chain(v)}
