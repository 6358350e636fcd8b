"""The program's spans on the device's clock (``chipbench.program_spans``),
on synthetic planes: one trainer thread, one refresh thread, one chip."""

from types import SimpleNamespace

import pytest

import chipbench_smoke  # noqa: F401  (puts the program and chipbench on the path)
from chipbench import program_spans as ps

MARK = ps.HOST_MARK


def host():
    """Host times in ns.  The trainer's thread, then the refresh worker's."""
    main = [
        ("bench/window", 0, 10000),
        (MARK, 100, 200),
        ("bench/step", 400, 9000),
        ("train", 500, 4000),
        ("train/dispatch", 500, 600),
        ("train/sync", 600, 2000),
        ("train/draw", 2000, 3900),
        ("lgd/draw", 2000, 3900),
        ("index/refresh_join", 2100, 3500),
        ("lgd/sample", 3500, 3800),
        (MARK, 9200, 9300),
    ]
    worker = [("index/refresh", 1000, 3400), ("index/embed", 1000, 3000)]
    return [main, worker]


def device(offset):
    """One chip whose clock reads ``offset`` ns ahead of the host's: each
    mark starts 50 ns after its dispatch and ends 50 ns before the host's
    next span boundary, so the marks bound the offset to within 50 ns on
    either side.  Idle, in host time: [1500, 1900] (train/sync, with the
    worker embedding), [2200, 2700] (the join, the worker still
    embedding), [3550, 3700] (lgd/sample) and [4200, 8000] (after the
    step, inside bench/step alone)."""
    d = lambda t: t + offset   # noqa: E731
    marks = [(d(150), d(350)), (d(9250), d(9950))]
    busy = [(d(350), d(1500)), (d(1900), d(2200)), (d(2700), d(3550)),
            (d(3700), d(4200)), (d(8000), d(9250))]
    return [{
        "XLA Modules": [("jit_bench_window_mark(1)", s, e - s)
                        for s, e in marks],
        "XLA Ops": [("%fusion.1 = f32[2] fusion(f32[2] %p)", s, e - s)
                    for s, e in busy]}]


@pytest.mark.parametrize("offset", [0, 1000, -5_000_000_000])
def test_marks_bound_the_offset_and_shift_the_spans(offset):
    r = ps.reduce(host(), device(offset))
    assert [used for *_, used in r.offsets] == [offset, offset]
    assert [(lo, up) for _, lo, up, _ in r.offsets] == \
        [(offset - 50, offset + 50)] * 2
    assert r.window_s == pytest.approx(8900e-9)
    # with the spans shifted onto the device's clock, the gaps read the
    # same whatever the offset
    assert [g[0] for g in r.gaps] == [
        "bench/step", "index/refresh_join+index/embed",
        "train/sync+index/embed", "lgd/sample"]
    assert [g[1] for g in r.gaps] == pytest.approx(
        [3800e-9, 500e-9, 400e-9, 150e-9])


def test_without_the_offset_the_gaps_would_be_misread():
    """A device clock 1000 ns ahead, read as if it were the host's: the
    gap in lgd/sample would fall after the step, outside every program
    span."""
    r = ps.reduce(host(), device(1000))
    assert r.gaps[3][0] == "lgd/sample"
    unshifted = ps.Timeline(ps.innermost(
        [x for x in host()[0] if ps.is_program(x[0])]))
    assert unshifted.at((4550 + 4700) / 2) is None   # its device times


def test_nested_spans_name_a_gap_by_the_innermost():
    r = ps.reduce(host(), device(0))
    names = dict((round(d * 1e9), n) for n, d, _ in r.gaps)
    # lgd/sample inside lgd/draw inside train/draw inside train
    assert names[150] == "lgd/sample"
    # the join inside lgd/draw, with the worker's embed appended
    assert names[500] == "index/refresh_join+index/embed"


def test_span_table_counts_totals_and_self_time():
    t = ps.reduce(host(), device(0)).totals
    assert t["train"] == (1, pytest.approx(3500e-9), pytest.approx(100e-9))
    assert t["lgd/draw"] == (1, pytest.approx(1900e-9),
                             pytest.approx(200e-9))
    assert t["index/refresh"] == (1, pytest.approx(2400e-9),
                                  pytest.approx(400e-9))
    assert t["index/embed"][:2] == (1, pytest.approx(2000e-9))
    assert "bench/step" not in t


def test_idle_is_split_by_the_spans_it_crosses():
    r = ps.reduce(host(), device(0))
    assert sum(r.idle_s.values()) == pytest.approx(4850e-9)
    assert r.idle_s["train/sync+index/embed"] == pytest.approx(400e-9)
    assert r.idle_s["bench/step"] == pytest.approx(3800e-9)


def test_a_program_without_spans_gives_nothing():
    bench_only = [[x for x in host()[0] if not ps.is_program(x[0])]]
    assert ps.reduce(bench_only, device(0)) is None


def test_metrics_read_the_window_or_nothing():
    from chipbench import metrics_io
    r = ps.reduce(host(), device(0))
    run = SimpleNamespace(trace=object(), steps=2, host_draw_s=0.0,
                          program_spans=r)
    read = {n: metrics_io.reader(n)(run) for n in (
        "host_sync_ms", "draw_dispatch_ms", "refresh_join_ms")}
    assert read["host_sync_ms"] == pytest.approx(1400e-9 * 1e3 / 2)
    assert read["refresh_join_ms"] == pytest.approx(1400e-9 * 1e3 / 2)
    assert read["draw_dispatch_ms"] == pytest.approx(500e-9 * 1e3 / 2)
    untraced = SimpleNamespace(trace=None, steps=2)
    older = SimpleNamespace(trace=object(), steps=2, program_spans=None)
    for name in read:
        assert metrics_io.reader(name)(untraced) is None
        assert metrics_io.reader(name)(older) is None


@pytest.mark.parametrize("offsets, at_500", [
    # a wide bound at the start takes the end's: one offset, -1.5
    ([(0, -2.0, 108.0), (1000, -1.8, -1.2)], 498.5),
    # bounds that share nothing: each end's middle, linear in between
    ([(0, 0.0, 10.0), (1000, 20.0, 30.0)], 515.0),
    # no lower bound at either end: the tighter upper one
    ([(0, None, 7.0), (1000, None, 3.0)], 503.0),
])
def test_host_to_device(offsets, at_500):
    assert ps.host_to_device(offsets)(500) == pytest.approx(at_500)
