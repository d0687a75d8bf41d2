"""The program's own phases as the reducers read them: nesting by containment,
self time, a span the window's edge cuts, counts, and a program without any
(the parent of the PR that brought them), on a table small enough to work out
by hand; then a traced CPU rehearsal that prints the eight metrics read from
them beside the ones the benchmark had."""
import pytest

from perfbench import program_spans, xplane
from perfbench.manifest import Manifest
from perfbench.program_spans import Span, Table
from perfbench.reducers import idle_outside_spans_pct, program_span_count, program_span_stat
from perfbench.tests.test_rehearse import checkout, rehearsal_of, run_cell  # noqa: F401

NEW = {"layout_count_s", "layout_fill_s", "layout_reuse_pct", "h2d_put_s", "h2d_put_gb",
       "fit_drain_wait_s", "fit_untraced_host_s", "fit_idle_unattributed_pct"}


def hand_made():
    """Two fits on thread line 0 (ns): the first 1000..2000, the second
    3000..4200, which the window 0..4000 cuts; another thread's span overlaps
    the first fit and must not become its child."""
    return Table([
        Span("train.fit", 1000, 1000, 0, {"rows": 8}),
        Span("train.layout", 1100, 400, 0, {"reused": 0, "rows": 8}),
        Span("train.layout.count", 1100, 100, 0),
        Span("train.layout.fill", 1250, 250, 0),
        Span("train.layout_put", 1500, 50, 0, {"bytes": 700}),
        Span("train.drain", 1600, 300, 0),
        Span("train.fit", 3000, 1200, 0, {"rows": 8}),
        Span("train.layout", 3100, 100, 0, {"reused": 1, "rows": 8}),
        Span("train.drain", 3900, 300, 0),          # 3900..4200: 100 inside the window
        Span("train.cache_put", 1200, 500, 1, {"bytes": 300}),  # another thread
    ])


class Ctx:
    """What a reducer reads of ``reduce.Context``."""

    def __init__(self, table, w0, w1, fits, ops=None):
        class Run:
            program_spans = table
        self.run, self.w0, self.w1 = Run(), w0, w1
        self.facts = {"fits": fits}
        self.trace = xplane.Trace(ops or {}, {}, [])
        self.dev = 0

    def per(self, unit):
        return self.facts.get(unit) or None


def test_nesting_is_by_containment_on_one_thread_line():
    t = hand_made()
    fit1, fit2 = t.named("train.fit")
    assert [c.name for c in fit1.children] == ["train.layout", "train.layout_put", "train.drain"]
    layout1 = fit1.children[0]
    assert [c.name for c in layout1.children] == ["train.layout.count", "train.layout.fill"]
    assert layout1.parent is fit1 and fit1.parent is None
    (other,) = t.named("train.cache_put")
    assert other.parent is None and other not in fit1.children
    assert [c.name for c in fit2.children] == ["train.layout", "train.drain"]


def test_self_time_is_duration_minus_what_the_children_cover():
    t = hand_made()
    fit1, _ = t.named("train.fit")
    assert fit1.self_inside(0, 4000) == 1000 - (400 + 50 + 300)
    assert fit1.children[0].self_inside(0, 4000) == 400 - (100 + 250)
    ctx = Ctx(t, 0, 4000, fits=2)
    # second fit inside the window: 1000, of which layout 100 and drain 100
    assert program_span_stat.reduce(ctx, "train.fit", self_time=True) == pytest.approx(
        (250 + 800) / 2 / 1e9)


def test_a_span_cut_by_the_windows_edge_counts_with_the_part_inside():
    ctx = Ctx(hand_made(), 0, 4000, fits=2)
    assert program_span_stat.reduce(ctx, "train.drain") == pytest.approx((300 + 100) / 2 / 1e9)
    assert program_span_stat.reduce(ctx, "train.fit") == pytest.approx((1000 + 1000) / 2 / 1e9)
    assert program_span_stat.reduce(ctx, ["train.cache_put", "train.layout_put"]) == \
        pytest.approx((500 + 50) / 2 / 1e9)
    # a window that starts inside the first fit's layout
    late = Ctx(hand_made(), 1300, 4000, fits=2)
    assert program_span_stat.reduce(late, "train.layout") == pytest.approx((200 + 100) / 2 / 1e9)
    assert program_span_stat.reduce(late, "train.layout.count") is None  # wholly before it


def test_counts_sum_and_share_over_the_events_that_start_in_the_window():
    ctx = Ctx(hand_made(), 0, 4000, fits=2)
    assert program_span_count.reduce(ctx, "train.layout", "reused", mode="share") == 50.0
    assert program_span_count.reduce(
        ctx, ["train.cache_put", "train.layout_put"], "bytes", per="fits", scale=1e-3
    ) == pytest.approx(0.5)
    first = Ctx(hand_made(), 0, 2500, fits=1)
    assert program_span_count.reduce(first, "train.layout", "reused", mode="share") == 0.0
    assert program_span_count.reduce(ctx, "train.layout", "no_such_stat") is None
    with pytest.raises(ValueError):
        program_span_count.reduce(ctx, "train.layout", "reused", mode="median")


def test_idle_outside_the_programs_spans():
    # the device is busy 1600..1900 and 3900..4000; the window is 0..4000
    ops = {0: [["step.1", 1600, 300], ["step.2", 3900, 100]]}
    ctx = Ctx(hand_made(), 0, 4000, fits=2, ops=ops)
    # idle 3600: outside any fit 0..1000 and 2000..3000 (2000); under train.fit
    # itself 1000..1100, 1550..1600, 1900..2000, 3000..3100, 3200..3900 (1050);
    # the rest (550) under a child: layout 1100..1500 and 3100..3200, layout_put
    # 1500..1550 (the other thread's span is no child of a fit and is left out)
    assert idle_outside_spans_pct.reduce(ctx, "train.fit") == pytest.approx(100 * 3050 / 3600)


def test_a_program_without_phases_gives_none_everywhere():
    ctx = Ctx(Table([]), 0, 4000, fits=2, ops={0: [["step.1", 1600, 300]]})
    assert program_span_stat.reduce(ctx, "train.fit") is None
    assert program_span_stat.reduce(ctx, ["train.cache_put", "train.layout_put"]) is None
    assert program_span_count.reduce(ctx, "train.layout", "reused", mode="share") is None
    assert idle_outside_spans_pct.reduce(ctx, "train.fit") is None


def test_the_table_is_read_once_per_run(monkeypatch):
    reads = []
    monkeypatch.setattr(program_spans, "read", lambda d: reads.append(d) or Table([]))

    class Run:
        trace_dir = "somewhere"

    run = Run()
    assert program_spans.of_run(run) is program_spans.of_run(run)
    assert reads == ["somewhere"]


def test_traced_rehearsal_prints_the_new_metrics_beside_the_old(checkout):  # noqa: F811
    name = "criteo_lr.fit_resident"
    out = rehearsal_of(run_cell(checkout, "--workload", name, "--seed", str(2**31 + 9),
                                "--seconds", "1", "--trace", "1", "--rehearse-on-cpu"))
    assert out["correct"] is True
    got = set(out["metrics"])
    assert NEW <= got <= set(Manifest().cell_metrics("per_layer", name))
    # the CPU's trace has no device plane: what reads the host's clock still prints
    assert {"pack_s", "layout_build_s", "fit_idle_pct", "fit_peak_hbm_gb"} <= got
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["layout_reuse_pct"] == 0.0
    assert 0 < m["layout_count_s"] < m["layout_fill_s"] < m["layout_build_s"] * 1.05
    assert m["h2d_put_gb"] > 0 and m["fit_drain_wait_s"] > 0
    assert 0 <= m["fit_idle_unattributed_pct"] <= 100
