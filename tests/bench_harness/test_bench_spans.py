"""The readers of the program's spans and of the counters they feed, each
on a hand-built run: the clock pairing and the idle account on events with
a known offset, the registry's growth over the window, and the silence of
every reader off the chip and on a program that records no ``fit``."""

import io

import pytest

from analytics_zoo_tpu.metrics import Tracer, set_tracer
from benchmark.manifest import Manifest
from benchmark.xplane import Event

MS = 1e3          # a span's microseconds
NS = 1e6          # a device event's nanoseconds, a millisecond
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ["feed_produce_ms_per_step", "epoch_sync_ms_per_fit",
       "fit_reentry_ms_per_fit", "context_init_s", "idle_explained_pct"]


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def spans(manifest):
    from benchmark.manifest import load_module
    import os

    return load_module(os.path.join(manifest.home, "layer_metrics",
                                    "_spans.py"))


class Ring:
    """A tracer filled by hand: spans in milliseconds, the main thread's
    and another's, ``fit`` and parents as ``span()`` would give them."""

    def __init__(self):
        self.tracer = Tracer(jax_bridge=False)
        self.ids = iter(range(1, 10_000))

    def add(self, name, start_ms, end_ms, parent=None, fit=None, tid=None,
            args=None):
        span_id = next(self.ids)
        self.tracer.add_event(name, start_ms * MS, (end_ms - start_ms) * MS,
                              args, id=span_id, parent_id=parent,
                              fit=span_id if fit == "own" else fit)
        if tid is not None:
            self.tracer._events[-1]["tid"] = tid
        return span_id

    def call(self, t0, steps=2, step_ms=10.0, enter_ms=4.0, sync_ms=0.5):
        """One ``fit`` call that starts at ``t0`` ms: 1 ms of
        ``zoo.keras.fit`` before ``zoo.fit``, ``enter_ms`` of entry (3 of
        them placement), then a step every ``step_ms``: 2 ms of wait, 1 ms
        of dispatch, 1 ms of bookkeeping, the rest the epoch's own.
        Returns its end."""
        end = t0 + 1 + enter_ms + steps * step_ms + sync_ms + 2 + 1
        root = self.add("zoo.keras.fit", t0, end + 1, fit="own")
        fit = self.add("zoo.fit", t0 + 1, end, root, root)
        enter = self.add("zoo.fit.enter", t0 + 1, t0 + 1 + enter_ms, fit,
                         root)
        self.add("zoo.fit.enter.place", t0 + 1, t0 + 4, enter, root)
        at = t0 + 1 + enter_ms
        epoch = self.add("zoo.train.epoch", at,
                         at + steps * step_ms + sync_ms + 2, fit, root)
        for k in range(steps):
            self.add("zoo.train.data_wait", at, at + 2, epoch, root)
            self.add("zoo.train.step_dispatch", at + 2, at + 3, epoch, root,
                     args={"step": k})
            self.add("zoo.train.on_iteration", at + 3, at + 4, epoch, root)
            self.add("zoo.feed.shard", at, at + 5, epoch, root, tid=77)
            at += step_ms
        self.add("zoo.train.epoch_sync", at, at + sync_ms, epoch, root)
        self.add("zoo.train.epoch_close", at + sync_ms, at + sync_ms + 2,
                 epoch, root)
        self.add("zoo.fit.exit", at + sync_ms + 2, end, fit, root)
        return end + 1


@pytest.fixture
def ring():
    ring = Ring()
    prev = set_tracer(ring.tracer)
    yield ring
    set_tracer(prev)


def _run(fits, **more):
    return {"window": {"fits": fits}, "device": TPU, "step_modules": {},
            "registry_before": {}, "registry_after": {}, **more}


# -- the window's calls, and a call by span -----------------------------

def test_the_window_s_calls_are_the_last_values_of_fit(ring, spans):
    ring.add("zoo.context.init", 0, 5)            # under no fit
    ends = [ring.call(t0) for t0 in (10, 100, 200)]
    calls = spans.window_calls(_run(2))
    assert len(calls) == 2
    assert [c[0]["name"] for c in calls] == ["zoo.keras.fit"] * 2
    assert [c[0]["ts"] for c in calls] == [100 * MS, 200 * MS]
    assert all(e["fit"] == c[0]["id"] for c in calls for e in c)
    assert spans.window_calls(_run(0)) == []
    assert len(spans.window_calls(_run(9))) == 3
    assert ends[0] < 100


def test_a_call_s_seconds_by_span_add_up_to_the_call(ring, spans):
    end = ring.call(0)
    (call,) = spans.window_calls(_run(1))
    by_span = spans.self_seconds(call)
    main = {name: s for (on_main, name), s in by_span.items() if on_main}
    assert sum(main.values()) == pytest.approx(end / 1e3)
    assert main["zoo.keras.fit"] == pytest.approx(0.002)
    assert main["zoo.fit"] == pytest.approx(0.0)
    assert main["zoo.fit.enter"] == pytest.approx(0.001)
    assert main["zoo.fit.enter.place"] == pytest.approx(0.003)
    assert main["zoo.train.epoch"] == pytest.approx(2 * 0.006)
    assert main["zoo.train.epoch_sync"] == pytest.approx(0.0005)
    # the feeder's thread is counted apart and takes nothing off the epoch
    assert by_span[(False, "zoo.feed.shard")] == pytest.approx(0.010)
    # leaves: all but enter's own millisecond and the epoch's own twelve
    assert spans.leaf_cover(call) == pytest.approx(1 - 13 / (end - 2))


# -- the clock ----------------------------------------------------------

@pytest.mark.parametrize("offset_ms, launch_ms, back_ms, how, bound", [
    (0.0, 0.3, None, "anchor", None),   # one clock: the latency remains
    (0.0, 0.3, 2.0, "anchor", None),
    (0.0, 40.0, 1.5, "anchor", None),   # pinned by the sync's side alone
    (0.0, 7.0, None, "paired", "upper"),    # no sync to hold it against
    (-0.4, 0.3, None, "paired", "upper"),   # a program before its dispatch
    (-1.79e12, 0.3, None, "paired", "upper"),   # counted from the capture
    (-1.79e12, 0.3, 2.0, "paired", "upper"),    # the two sides agree
    (-1.79e12, 40.0, 1.5, "paired-sync", "lower"),  # the batch's transfer
    (5000.0, 40.0, 1.5, "paired-sync", "lower")])
def test_clock_pairing_finds_a_known_offset(spans, offset_ms, launch_ms,
                                            back_ms, how, bound):
    """Six steps of 10 ms dispatched 1 ms apart: the chip is idle at the
    first alone, ``launch_ms`` after its dispatch opens; the sync returns
    ``back_ms`` after the last program ends."""
    offset = round(offset_ms * NS)
    dispatch = [round(k * NS) for k in range(6)]
    first = dispatch[0] + round(launch_ms * NS)
    programs = [first + offset + k * 10_000_000 for k in range(6)]
    synced = [] if back_ms is None else [
        (programs[-1] + 10_000_000 - offset + round(back_ms * NS),
         programs[-1] + 10_000_000)]
    got, got_how, lower, upper = spans.clock_offset_ns(dispatch, programs,
                                                       synced)
    assert got_how == how
    assert upper == offset + round(launch_ms * NS)
    assert lower == (float("-inf") if back_ms is None
                     else offset - round(back_ms * NS))
    assert got == {None: 0, "upper": upper, "lower": lower}[bound]
    # no program starts before its shifted dispatch opens, no sync
    # returns before its program ends
    assert all(p >= d + got for d, p in zip(dispatch, programs))
    assert all(sync + got >= end for sync, end in synced)


def test_clock_pairing_needs_a_program_a_dispatch(spans):
    assert spans.clock_offset_ns([1, 2], [1.5]) is None
    assert spans.clock_offset_ns([], []) is None
    # a sync that returns before its program could have: not these spans
    assert spans.clock_offset_ns([0], [5.0], [(10, 20.0)]) is None


# -- the idle account ---------------------------------------------------

def test_idle_seconds_go_to_the_innermost_open_span(spans):
    held = [(0, 100 * NS, "zoo.fit"), (10 * NS, 40 * NS, "zoo.fit.enter"),
            (20 * NS, 30 * NS, "zoo.fit.enter.place"),
            (120 * NS, 130 * NS, "zoo.fit")]
    gaps = [(5 * NS, 50 * NS), (90 * NS, 125 * NS), (7 * NS, 7 * NS)]
    by_span = spans.idle_by_span(gaps, held)
    assert by_span == pytest.approx({
        "zoo.fit": (5 + 10 + 10 + 5) / 1e3,      # 5-10, 40-50, 90-100, 120-5
        "zoo.fit.enter": (10 + 10) / 1e3,        # 10-20 and 30-40
        "zoo.fit.enter.place": 10 / 1e3,
        spans.UNDER_NO_SPAN: 20 / 1e3})          # 100-120
    assert sum(by_span.values()) == pytest.approx(0.080)


@pytest.mark.parametrize("offset_ms", [0.0, -1.79e12, 250.0])
def test_idle_explained_on_events_with_a_known_offset(ring, manifest,
                                                      offset_ms):
    """Two calls of two steps; the device runs each step for 8 ms from
    0.2 ms after its dispatch opens (the chip is never busy at a
    dispatch), on a clock ``offset_ms`` from the host's."""
    t1 = ring.call(1000)
    ring.add("between the calls", t1, t1 + 3)       # under no fit
    ring.call(t1 + 3)
    clock = ring.tracer.device_clock_ns
    dispatch = sorted(clock(e)[0] for e in ring.tracer.events()
                      if e["name"] == "zoo.train.step_dispatch")
    shift = round((offset_ms + 0.2) * NS)
    modules = [Event("jit_train_step", d + shift, 8_000_000)
               for d in dispatch]     # whole nanoseconds
    run = _run(2, step_modules={"/device:TPU:0": modules})
    err = io.StringIO()
    read = manifest.reader("idle_explained_pct")
    value = read(run, out=err)
    lines = err.getvalue().splitlines()
    how, offset = lines[0].split()[1:3]
    assert lines[0].split()[3] == "between"
    assert how == ("anchor" if offset_ms == 0.0 else "paired")
    assert int(offset) == (0 if how == "anchor" else shift)
    gaps = {name: float(s) for _, name, s in map(str.split, lines[1:])}
    # the pairing takes the tightest pair's launch latency for offset, so
    # under it the spans stand 0.2 ms late against the device
    late = 0.0 if how == "anchor" else 0.2
    # within a call a program ends 8.2 ms after its dispatch opened and
    # the next starts 10 ms after it: the next step's wait but its first
    # 0.2 ms, and its dispatch until the program starts
    # between the calls: the sync's last 0.3 ms, close 2, exit 1,
    # the root's own 1 + 1, 3 under no span, entry 3 + 1, the wait's 2
    expected = {"zoo.train.data_wait": 2 * (1.8 + late) + 2.0,
                "zoo.train.step_dispatch": 3 * (0.2 - late),
                "zoo.train.epoch_sync": 0.3 + late,
                "zoo.train.epoch_close": 2.0, "zoo.fit.exit": 1.0,
                "zoo.keras.fit": 2.0, "(none)": 3.0,
                "zoo.fit.enter.place": 3.0, "zoo.fit.enter": 1.0}
    assert gaps == pytest.approx(
        {k: v / 1e3 for k, v in expected.items() if v}, abs=1e-9)
    assert list(gaps.values()) == sorted(gaps.values(), reverse=True)
    idle = sum(float(line.split()[-1]) for line in lines[1:])
    between = sum((b.start_ns - a.end_ns) / 1e9
                  for a, b in zip(modules, modules[1:]))
    assert idle == pytest.approx(between)
    assert value == pytest.approx(100.0 * (1 - 3e-3 / between), abs=1e-3)
    # a second chip with the same programs: the same share
    run["step_modules"]["/device:TPU:1"] = modules
    assert read(run, out=io.StringIO()) == pytest.approx(value)


def test_idle_explained_needs_a_program_a_dispatch(ring, manifest):
    ring.call(0)
    read = manifest.reader("idle_explained_pct")
    one = [Event("jit_train_step", 0.0, 8 * NS)]
    assert read(_run(1, step_modules={"/device:TPU:0": one}),
                out=io.StringIO()) is None
    assert read(_run(1), out=io.StringIO()) is None


# -- the host-clock readers ---------------------------------------------

def test_fit_reentry_is_sync_s_end_to_the_next_first_dispatch(
        ring, manifest, capsys):
    t1 = ring.call(0, sync_ms=12.0)
    t2 = ring.call(t1 + 5, enter_ms=6.0, sync_ms=12.0)
    ring.call(t2, enter_ms=4.0, sync_ms=12.0)
    # close 2, exit 1, root 1, (5 between), root 1, entry 6, wait 2: 18;
    # then 2 + 1 + 1 + 0 + 1 + 4 + 2: 11
    read = manifest.reader("fit_reentry_ms_per_fit")
    assert read(_run(3)) == pytest.approx((18 + 11) / 2)
    err = capsys.readouterr().err.splitlines()
    rows = {tuple(line.split()[1:3]): float(line.split()[3])
            for line in err if line.startswith("fit ")}
    assert rows[("main", "zoo.train.epoch_sync")] == pytest.approx(0.012)
    assert rows[("other", "zoo.feed.shard")] == pytest.approx(0.010)
    assert [line for line in err if line.startswith("cover ")]
    # most first
    seconds = [float(line.split()[3]) for line in err
               if line.startswith("fit ")]
    assert seconds == sorted(seconds, reverse=True)
    # the window's last two calls alone: one boundary
    assert read(_run(2)) == pytest.approx(11)
    assert read(_run(1)) is None


def _grown(name, before, after):
    return {"registry_before": {(name, ""): before},
            "registry_after": {(name, ""): after}}


def test_feed_produce_is_gather_plus_shard_a_queue_item(manifest):
    read = manifest.reader("feed_produce_ms_per_step")
    run = _run(2)
    run["registry_before"] = {("zoo_feed_host_batch_seconds", ""): (1.0, 4),
                              ("zoo_feed_shard_seconds", ""): (2.0, 3)}
    run["registry_after"] = {("zoo_feed_host_batch_seconds", ""): (1.9, 70),
                             ("zoo_feed_shard_seconds", ""): (4.66, 67)}
    # 0.9 s of gather and 2.66 s of shard over 64 items; the gather's two
    # exhausted probes are no items
    assert read(run) == pytest.approx((0.9 + 2.66) / 64 * 1e3)
    run["registry_after"] = dict(run["registry_before"])
    assert read(run) is None


def test_epoch_sync_is_the_growth_a_call(manifest):
    read = manifest.reader("epoch_sync_ms_per_fit")
    run = {**_run(6), **_grown("zoo_train_epoch_sync_seconds",
                               (0.5, 3), (12.5, 9))}
    assert read(run) == pytest.approx(2000.0)


def test_context_init_is_the_gauge_at_the_end_of_set_up(manifest):
    read = manifest.reader("context_init_s")
    run = {**_run(6), **_grown("zoo_context_init_seconds",
                               (11.25, 1), (0.001, 1))}
    assert read(run) == pytest.approx(11.25)


# -- silence ------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_gives_nothing_to_read(manifest, metric):
    """The parent's program: no ``fit`` on an event, no new family in the
    registry.  The reader returns nothing and does not raise."""
    tracer = Tracer(jax_bridge=False)
    for k in range(4):
        tracer.add_event("zoo.train.step_dispatch", k * 10 * MS, MS)
        del tracer._events[-1]["fit"]
    prev = set_tracer(tracer)
    try:
        modules = [Event("jit_train_step", k * 10 * NS, 8 * NS)
                   for k in range(4)]
        run = _run(2, step_modules={"/device:TPU:0": modules},
                   registry_before={("zoo_compile_seconds", "x"): (1.0, 1)},
                   registry_after={("zoo_compile_seconds", "x"): (1.0, 1)})
        assert manifest.reader(metric)(run) is None
    finally:
        set_tracer(prev)


@pytest.mark.parametrize("metric", NEW)
def test_off_the_chip_the_new_readers_stay_silent(ring, manifest, metric):
    """They time the host against a chip; a CPU run at toy size has no
    such time to give."""
    ring.call(ring.call(0))
    run = _run(2, device={"platform": "cpu", "kind": "cpu", "count": 8})
    for name in ("zoo_feed_host_batch_seconds", "zoo_feed_shard_seconds",
                 "zoo_train_epoch_sync_seconds",
                 "zoo_context_init_seconds"):
        run["registry_before"][(name, "")] = (0.0, 0)
        run["registry_after"][(name, "")] = (1.0, 8)
    assert manifest.reader(metric)(run) is None


def _holds_run(names, run):
    """``run`` stands in ``names`` as it is, one name after the other."""
    return any(names[i:i + len(run)] == run
               for i in range(len(names) - len(run) + 1))


def test_the_new_metrics_are_appended_and_report_in_both_cells(manifest):
    """PR 25's five stand together and in their order, in the list and in
    every cell's; what a later PR appends comes after them."""
    assert _holds_run([m["name"] for m in manifest.doc["per_layer"]], NEW)
    for cell in manifest.doc["workloads"]:
        assert _holds_run([m["name"] for m
                           in manifest.per_layer(cell["name"])], NEW)
    assert not _holds_run(NEW[:2] + ["other"] + NEW[2:], NEW)
