"""The reduction of MegaScan's spans and the chip's program executions: on
made-up spans, on the trace recorded on a TPU v5e
(``bench/tools/record_probe_trace.py``), and on a traced smoke-size run of
the serving runner on the CPU."""

from __future__ import annotations

import time

import pytest

from conftest import DATA

from benchlib import megascan
from benchlib.megascan import Span, Spans
from benchlib.xplane import Op

PROBE = DATA / "probe.xplane.pb"


def tick(start, end, *calls, name="megascan.tick"):
    return [Span(name, start, end, {})] + [
        Span("megascan." + c, s, e, {}) for c, s, e in calls]


def test_tick_host_time_is_the_tick_less_its_device_calls():
    sp = Spans(host=sorted(
        # 10 ms tick: prefill 1-4 ms, decode 5-8 ms -> 4 ms of host work
        tick(0, 10_000_000, ("prefill", 1_000_000, 4_000_000),
             ("decode", 5_000_000, 8_000_000), ("admit", 0, 1_000_000))
        # 6 ms tick, overlapping calls counted once: 6 - 4 = 2 ms
        + tick(20_000_000, 26_000_000,
               ("prefill_chunk", 21_000_000, 24_000_000),
               ("verify", 22_000_000, 25_000_000))
        # a tick that called no device: not counted
        + tick(30_000_000, 30_500_000, ("publish", 30_100_000, 30_200_000))
        # the odd count makes the median one tick's: 8 - 2 = 6 ms
        + tick(40_000_000, 48_000_000, ("decode", 41_000_000, 43_000_000)),
        key=lambda o: o.start))
    assert megascan.tick_host_ms(sp) == pytest.approx(4.0)
    assert megascan.tick_host_ms(Spans()) is None
    assert megascan.tick_host_ms(Spans(host=tick(0, 5, ("admit", 1, 2)))) is None


def test_program_time_and_its_part_outside_a_kernel():
    mods = {0: [Op("jit_serve_decode(123)", 0, 100), Op("jit_serve_prefill(9)", 100, 150),
                Op("jit_serve_decode(123)", 200, 330)]}
    ops = {0: [Op("%paged_attention_pallas.9 = custom-call", 10, 40),
               Op("%fusion.3 = fusion", 40, 90),
               Op("%paged_attention_pallas.9 = custom-call", 60, 70),
               Op("%paged_prefill_pallas.2 = custom-call", 110, 140),
               Op("%paged_attention_pallas.9 = custom-call", 210, 250)]}
    sp = Spans(modules=mods)
    assert megascan.module_ms(sp, "jit_serve_decode") == pytest.approx(
        (100 + 130) / 2 / 1e6)
    assert megascan.module_ms(sp, "jit_serve_prefill") == pytest.approx(50 / 1e6)
    # decode runs: 100 - 40 = 60 ns and 130 - 40 = 90 ns outside the kernel
    assert megascan.module_outside_ms(
        sp, ops, "jit_serve_decode", "paged_attention_pallas") == pytest.approx(
        75 / 1e6)
    # a program name that is a prefix of another does not match it
    assert megascan.module_ms(sp, "jit_serve") is None
    assert megascan.module_ms(Spans(), "jit_serve_decode") is None
    assert megascan.module_outside_ms(Spans(), ops, "jit_serve_decode",
                                      "paged_attention_pallas") is None


@pytest.fixture(scope="module")
def probe():
    if not PROBE.is_file():
        pytest.fail(f"missing recorded trace {PROBE}")
    return megascan.load(str(PROBE), prefix="probe")


def test_recorded_trace_programs_and_host_spans(probe):
    from benchlib import xplane

    names = [m.name.split("(")[0] for m in probe.modules[0]]
    assert sorted(set(names)) == ["jit__lambda", "jit_wrapped"]
    assert names.count("jit__lambda") == 3 and names.count("jit_wrapped") == 3
    assert [s.name for s in probe.host].count("probe_sleep") == 3
    # the Pallas kernel fills most of each run of its program
    ops = xplane.load(str(PROBE)).devices
    whole = megascan.module_ms(probe, "jit_wrapped")
    outside = megascan.module_outside_ms(probe, ops, "jit_wrapped",
                                         "probe_kernel")
    assert 0 <= outside < whole
    assert megascan.module_ms(probe, "jit__lambda") > whole


def test_spans_of_a_traced_smoke_run(smoke_root, tmp_path):
    import jax

    from benchlib import serve, xplane
    from benchlib.device import Peak
    from benchlib.spec import load_cell

    cell = load_cell("serve-smoke", smoke_root)
    res = serve.run_cell(cell, seed=2**33 + 5, seconds=2, trace=True,
                         t_start=time.perf_counter(), devs=jax.devices()[:1],
                         peak=Peak("cpu-test", 1e12, 1e11, 1e10, "test value"),
                         trace_root=str(tmp_path))
    assert res["correct"] is True
    sp = megascan.load(xplane.find_xplane(str(tmp_path)))
    names = {s.name for s in sp.host}
    assert {"megascan.tick", "megascan.admit", "megascan.upload",
            "megascan.decode", "megascan.pull", "megascan.publish",
            "megascan.plugins"} <= names
    assert all(s.stats.get("rank") == 0 for s in sp.host)
    assert megascan.tick_host_ms(sp) > 0
    # the CPU trace has no TPU plane: no program executions to read
    assert sp.modules == {}
    assert megascan.module_ms(sp, "jit_serve_decode") is None
