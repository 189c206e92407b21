"""Trace reducers and per-layer readers, on a hand-made trace whose answers
are known and on a small trace recorded on a TPU v5e."""
import importlib
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace as T  # noqa: E402

RECORDED = os.path.join(BENCH, "data", "trace_tpu_v5e.json")
RECORDED_HOST = os.path.join(BENCH, "data", "trace_tpu_v5e_hoststream.json")

# window [0, 1000] ns; device busy on [100, 300] ∪ [400, 450] ∪ [600, 900]
HAND = T.Trace(
    ops=[["%ebe_element_lanes_pallas.3 = f32[2,30,8] custom-call(f32[2,30,8] %x)", 100, 100], ["fusion.1", 150, 110], ["%vmap_jit_multispring_pallas__.1 = (f32[2,6,8]) custom-call(f32[2,6,8] %e)", 250, 50],
         ["%copy-start.3 = (f32[8], f32[8]) copy-start(f32[8]{0:S(5)} %c)", 400, 50], ["fusion.2", 600, 300], ["fusion.9", 1200, 50]],
    spans=[["bench.window", 0, 1000], ["bench.call", 0, 500], ["bench.fetch", 500, 500]],
    device="/device:TPU:0", lines={"XLA Ops": 6, "Async XLA Ops": 6},
    # host-link copies of 32 B: three to the device issued together and
    # done 32 ns apart, one back; one that also waited on the program; one
    # done after the window
    async_ops=[[f"%copy-start.{i} = (f32[8]{{0}}, f32[8]{{0:S(5)}}, u32[]{{:S(2)}}) "
                f"copy-start(f32[8]{{0:S(5)}} %c{i})", 400, 32 * i] for i in (1, 2, 3)]
    + [["%copy-start.4 = (f32[8]{0:S(5)}, f32[8]{0}, u32[]{:S(2)}) copy-start(f32[8]{0} %d)", 600, 32],
       ["%copy-start.5 = (f32[2,4]{1,0}, f32[2,4]{1,0:S(5)}, u32[]{:S(2)}) copy-start(f32[2,4]{1,0:S(5)} %e)", 100, 800],
       ["%copy-start.6 = (f32[8]{0:S(5)}, f32[8]{0}, u32[]{:S(2)}) copy-start(f32[8]{0} %f)", 900, 300]])


def ctx_for(tr, **kw):
    c = dict(trace=tr, case_steps=2, steps=1, cases=2, iters=[3, 5, 4, 4],
             device_kind="TPU v5 lite", host_link_bytes=2e9, notes={},
             config={"n_elem": 63888, "n_nodes": 91125, "nspring": 150})
    c.update(kw)
    return types.SimpleNamespace(**c)


def read(name, ctx):
    return importlib.import_module(f"metrics.{name}").read(ctx)


def test_union_idle_and_gaps_by_host_activity():
    assert T.union(HAND.ops[:3]) == [(100, 300)]
    busy, win = T.busy_idle(HAND)
    assert busy == pytest.approx(550e-9) and win == pytest.approx(1000e-9)
    assert read("device_idle_share", ctx_for(HAND)) == pytest.approx(45.0)
    gs = T.gaps(HAND)
    assert gs == [(0, 100), (300, 400), (450, 600), (900, 1000)]
    bd = T.breakdown(HAND)
    assert bd["idle_gaps"][0] == ["bench.fetch", pytest.approx(150e-9)]
    assert sorted(n for n, _ in bd["idle_gaps"]) == [
        "bench.call", "bench.call", "bench.fetch", "bench.fetch"]
    assert bd["device_ops"][0] == ["fusion.2", pytest.approx(300e-9)]
    assert "fusion.9" not in dict(bd["device_ops"])  # outside the window


def test_kernel_link_and_glue_times():
    c = ctx_for(HAND)
    assert read("ebe_kernel_ms_per_step", c) == pytest.approx(100e-6 / 2)
    assert read("multispring_kernel_ms_per_step", c) == pytest.approx(50e-6 / 2)
    assert read("host_link_wait_ms_per_step", c) == pytest.approx(50e-6)
    # per direction, from the later of issue and the last completion: 32 B
    # in 32 ns four times, and the copy that waited 404 ns; the median
    assert read("host_link_gb_per_s", c) == pytest.approx(1.0)
    assert read("host_link_ms_per_step", c) == pytest.approx(2.0 / 1.0 * 1e3)
    assert read("glue_ms_per_step", c) == pytest.approx(410e-6 / 2)
    assert read("host_link_gb_per_step", c) == pytest.approx(2.0)
    assert read("cg_iters_per_step", c) == pytest.approx(4.0)


def test_readers_return_nothing_where_nothing_ran():
    empty = T.Trace(ops=[["fusion.1", 10, 5]], spans=[["bench.window", 0, 100]])
    c = ctx_for(empty, host_link_bytes=0.0, iters=[])
    for name in ("ebe_kernel_ms_per_step", "ebe_kernel_roofline",
                 "multispring_kernel_ms_per_step", "multispring_kernel_roofline",
                 "host_link_wait_ms_per_step", "host_link_gb_per_s",
                 "host_link_ms_per_step", "host_link_gb_per_step", "cg_iters_per_step"):
        assert read(name, c) is None, name


def test_roofline_readers_use_least_work_over_kernel_time():
    from work import ebe_product, multispring_update

    c = ctx_for(HAND)
    fl, by = ebe_product.count(63888, 91125)
    want = 100 * (2 * by / 819e9) / 100e-9   # one event, two cases
    assert read("ebe_kernel_roofline", c) == pytest.approx(want)
    assert c.notes["ebe_kernel_roofline"].startswith("memory bound")
    fl, by = multispring_update.count(63888, 150)
    assert read("multispring_kernel_roofline", c) == pytest.approx(
        100 * (2 * by / 819e9) / 50e-9)


def test_recorded_tpu_trace():
    """The first 0.25 s of a traced ebe-k2-resident window on a TPU v5e
    (while loops already left out): the EBE kernel's events are found by
    name, and kernel, glue and idle add up to the window."""
    with open(RECORDED) as f:
        tr = T.Trace.from_json(f.read())
    assert not [o for o in tr.ops if T.opcode(o[0]) in T.CONTAINERS]
    busy, win = T.busy_idle(tr)
    assert win == pytest.approx(0.25)
    c = ctx_for(tr, case_steps=2, steps=1)
    ebe = read("ebe_kernel_ms_per_step", c)
    glue = read("glue_ms_per_step", c)
    idle = read("device_idle_share", c)
    assert ebe == pytest.approx(1.0120555)
    assert glue == pytest.approx(123.816543)
    assert idle == pytest.approx(0.1371212, abs=1e-6)
    # kernel and glue intervals do not overlap: together they are the busy time
    assert (ebe + glue) * 2 == pytest.approx(busy * 1e3)
    assert read("multispring_kernel_ms_per_step", c) is None  # not in these 0.25 s
    share = read("ebe_kernel_roofline", c)
    assert 0 < share < 100 and c.notes["ebe_kernel_roofline"] == "memory bound, 8 products"
    bd = T.breakdown(tr)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert all(n.split()[1] == "fusion" for n, _ in bd["device_ops"])
    assert json.loads(json.dumps(bd)) == bd


def test_recorded_host_stream_trace():
    """0.11 s of a traced crs-hoststream window on a TPU v5e, across the
    boundary of two steps: each step's last spring-state blocks go back to
    host memory and the next step's first come in, 7,372,800 B a copy,
    while the device waits.  The link's rate is read from the copies'
    completions; kernel, glue and the waits on the link add up to the busy
    time."""
    from work import host_link

    with open(RECORDED_HOST) as f:
        tr = T.Trace.from_json(f.read())
    c = ctx_for(tr, case_steps=1, steps=1, host_link_bytes=0.7077888e9,
                config={"n_elem": 24576, "n_nodes": 35937, "nspring": 150})
    copies = importlib.import_module("metrics.host_link_gb_per_s").transfers(c)
    assert len(copies) == 86 and {b for b, _ in copies} == {7372800}
    assert {host_link.from_host(o[0]) for o in tr.async_ops if "S(5)" in o[0]} == {True, False}
    rate = read("host_link_gb_per_s", c)
    assert rate == pytest.approx(14.543044, rel=1e-6)
    assert read("host_link_ms_per_step", c) == pytest.approx(707.7888 / rate)
    wait = read("host_link_wait_ms_per_step", c)
    glue = read("glue_ms_per_step", c)
    ms = read("multispring_kernel_ms_per_step", c)
    assert wait == pytest.approx(35.445023)
    assert glue == pytest.approx(72.956252)
    busy, _ = T.busy_idle(tr)
    assert wait + glue + ms == pytest.approx(busy * 1e3)
    assert read("device_idle_share", c) == pytest.approx(2.1720057, abs=1e-6)
