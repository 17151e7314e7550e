"""The model step by part (``layer_metrics/_parts.py``): the rule that
classes an operation by the program's three names, the account on
hand-built events (a loop whose children are the head's, an operation made
again in the feed-forward, one with a scope and no part, one with no
scope: all of it and the idle inside add up to the program to the
nanosecond), each reader that this brought into ``BENCHMARK.json`` on a
hand-built run, silent on a capture without the names, and the account of
a kept capture from the command line."""

import json
import os

import pytest

from benchmark.manifest import Manifest, load_module
from benchmark.xplane import Capture, Event

MS = 1_000_000      # a device event's nanoseconds, a millisecond
PLANE = "/device:TPU:0"
STEP = "jit(train_step)/"
MIXER_FWD = STEP + "jvp(zoo.mixer)/jit(_flash_fwd_pallas)/pallas_call"
HEAD_LOOP = STEP + "jvp(zoo.head)/while"
HEAD_BODY = HEAD_LOOP + "/body/closed_call/dot_general"
FFN_AGAIN = STEP + ("transpose(jvp(jvp()))/checkpoint/rematted_computation/"
                    "zoo.ffn/dot_general")
FFN_BWD = STEP + "transpose(jvp(zoo.ffn))/while/body/jit(gmm)/pallas_call"
OPTIMIZER = STEP + "add"
PART_READERS = {"step_mixer_ms": "zoo.mixer", "step_ffn_ms": "zoo.ffn",
                "step_head_ms": "zoo.head"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def parts(manifest):
    return load_module(os.path.join(manifest.home, "layer_metrics",
                                    "_parts.py"))


def test_the_rule_that_classes_an_operation(parts):
    assert parts.part_of(MIXER_FWD) == parts.MIXER
    assert parts.part_of(FFN_AGAIN) == parts.FFN
    assert parts.part_of(FFN_BWD) == parts.FFN
    assert parts.part_of(HEAD_LOOP) == parts.HEAD
    assert parts.part_of(STEP + "transpose(jvp(zoo.head))/dot_general") \
        == parts.HEAD
    # the innermost name where a path (or a fusion's two) holds more
    assert parts.part_of(STEP + "jvp(zoo.head)/zoo.mixer/add") == parts.MIXER
    assert parts.part_of(MIXER_FWD + ";" + FFN_AGAIN) == parts.FFN
    assert parts.part_of(OPTIMIZER) == parts.REST
    assert parts.part_of(STEP + "jvp(jit(_take))/gather") == parts.REST
    assert parts.part_of(STEP + "transpose(jvp(jvp()))/remat2") == parts.REST
    assert parts.part_of("") == parts.NO_SCOPE
    # the names are the program's, spelled here
    from analytics_zoo_tpu.metrics import tracing

    assert (parts.MIXER, parts.FFN, parts.HEAD) == (
        tracing.MIXER_SCOPE, tracing.FFN_SCOPE, tracing.HEAD_SCOPE)


def _program(t):
    """One step program of 20 ms at ``t`` ms: a mixer kernel 3; the head's
    loop of 6, 4 of them its two children's; 1 idle; made again in the
    feed-forward 2; a backward call in the feed-forward 2.5; the
    optimizer's own 1.5; a copy with no scope 1; 3 idle at the end."""
    return [Event("_flash_fwd_pallas.1/tpu_custom_call/3", t * MS, 3 * MS,
                  MIXER_FWD),
            Event("while.2", (t + 3) * MS, 6 * MS, HEAD_LOOP),
            Event("fusion.3", (t + 4) * MS, 2 * MS, HEAD_BODY),
            Event("fusion.3", (t + 6) * MS, 2 * MS, HEAD_BODY),
            Event("fusion.4", (t + 10) * MS, 2 * MS, FFN_AGAIN),
            Event("gmm.5/tpu_custom_call/1", (t + 12) * MS, 2.5 * MS,
                  FFN_BWD),
            Event("fusion.6", (t + 14.5) * MS, 1.5 * MS, OPTIMIZER),
            Event("copy-done.7", (t + 16) * MS, 1 * MS)]


def _run(ops, programs, planes=(PLANE,)):
    return {"capture": Capture({p: ops for p in planes}, {}),
            "step_modules": {p: programs for p in planes}}


def test_the_parts_the_rest_and_the_idle_add_up_to_the_program(parts):
    programs = [Event("jit_train_step", t * MS, 20 * MS) for t in (10, 40)]
    ops = _program(10) + _program(40)
    # an operation outside every step program is no part's
    ops.append(Event("fusion.9", 70 * MS, 5 * MS, MIXER_FWD))
    found = parts.account(ops, programs)
    ns = found["ns"]
    assert dict(ns) == {
        parts.MIXER: 6 * MS, parts.HEAD: 12 * MS, parts.FFN: 9 * MS,
        parts.REST: 3 * MS, parts.NO_SCOPE: 2 * MS, parts.IDLE: 8 * MS,
        parts.PROGRAM: 40 * MS}
    # to the nanosecond
    assert sum(ns[c] for c in parts.CLASSES) + ns[parts.IDLE] \
        == ns[parts.PROGRAM]
    assert dict(found["by_phase"]) == {
        (parts.MIXER, "forward"): 6 * MS, (parts.HEAD, "forward"): 12 * MS,
        (parts.FFN, "made again"): 4 * MS, (parts.FFN, "backward"): 5 * MS,
        (parts.REST, "optimizer"): 3 * MS,
        (parts.NO_SCOPE, "no scope"): 2 * MS}
    assert dict(found["rest"]) == {"fusion.6": 3 * MS}
    assert found["scopes"] == {"fusion.6": OPTIMIZER}
    assert dict(found["unnamed"]) == {"copy-done.7": 2 * MS}
    # a loop keeps what its children leave: a feed-forward child of the
    # head's loop takes its time out of the head
    ops[2] = Event("fusion.3", 14 * MS, 2 * MS, FFN_AGAIN)
    ns = parts.account(ops, programs)["ns"]
    assert (ns[parts.HEAD], ns[parts.FFN]) == (10 * MS, 11 * MS)


@pytest.mark.parametrize("metric", sorted(PART_READERS))
def test_a_part_reader_on_a_hand_built_run(manifest, parts, metric):
    want = {"zoo.mixer": 3.0, "zoo.ffn": 4.5,
            "zoo.head": 6.0}[PART_READERS[metric]]
    read = manifest.reader(metric)
    programs = [Event("jit_train_step", t * MS, 20 * MS) for t in (10, 40)]
    run = _run(_program(10) + _program(40), programs)
    assert read(run) == pytest.approx(want)
    # the account is made once a run
    assert run["step_parts"]["programs"] == 2
    report = parts.report(run["step_parts"])
    assert report["ms"] == pytest.approx({
        "zoo.mixer": 3.0, "zoo.ffn": 4.5, "zoo.head": 6.0,
        "scoped rest": 1.5, "no scope": 1.0, "idle inside": 4.0,
        "program": 20.0})
    assert report["by_phase"]["zoo.ffn"] == pytest.approx(
        {"made again": 2.0, "backward": 2.5})
    assert report["rest"] == [["fusion.6", pytest.approx(1.5), OPTIMIZER]]
    # two chips, each with these programs: the same mean
    both = _run(_program(10) + _program(40), programs,
                (PLANE, "/device:TPU:1"))
    assert read(both) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(PART_READERS))
def test_a_part_reader_is_silent_without_its_name(manifest, metric):
    read = manifest.reader(metric)
    programs = [Event("jit_train_step", 10 * MS, 20 * MS)]
    # the parent's program: the same operations, no name in their scopes
    unnamed = [e._replace(scope=e.scope.replace("zoo.", "layer."))
               for e in _program(10)]
    assert read(_run(unnamed, programs)) is None
    # a model without that part (a Dense head is no ``zoo.head``)
    without = [e for e in _program(10)
               if PART_READERS[metric] not in e.scope]
    assert read(_run(without, programs)) is None
    # no device plane, or no step program on it
    assert read({"capture": None, "step_modules": {}}) is None
    assert read(_run([], {})) is None
    assert read({"capture": Capture({PLANE: _program(10)}, {}),
                 "step_modules": {PLANE: []}}) is None


def test_the_entries_are_appended_with_their_cells(manifest):
    names = [m["name"] for m in manifest.doc["per_layer"]]
    entries = {m["name"]: m for m in manifest.doc["per_layer"]}
    appended = ["step_mixer_ms", "step_ffn_ms", "step_head_ms"]
    assert names[names.index("kda_scan_roofline") + 1:][:3] == appended
    decoders = ["gpt2-small-fit", "ouro-2.6b-fit", "kanana-2-30b-a3b-fit",
                "kimi-linear-48b-a3b-fit"]
    for name in appended:
        assert entries[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": "train_examples_per_s",
            "workloads": decoders[1:] if name == "step_head_ms"
            else decoders}


def test_the_account_of_a_kept_capture_from_the_command_line(
        parts, tmp_path, capsys):
    """``_parts.py <capture>``: what the builder runs to write PERF.md."""
    from jax.profiler import ProfileData

    ops = _program(10)
    said = {e.name.split("/")[0]: e.scope for e in ops}
    metadata = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} '
        f'name: "%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)" '
        f'display_name: "{name}" '
        + (f'stats {{ metadata_id: 1 str_value: "{scope}:" }} '
           if scope else "") + "} }\n"
        for i, (name, scope) in enumerate(said.items(), 1))
    index = {name: i for i, name in enumerate(said, 1)}
    events = "".join(
        f"events {{ metadata_id: {index[e.name.split('/')[0]]} "
        f"offset_ps: {int(e.start_ns * 1000)} "
        f"duration_ps: {int(e.dur_ns * 1000)} }}\n" for e in ops)
    text = f'''
planes {{
  name: "{PLANE}"
  lines {{ name: "XLA Ops" timestamp_ns: 0
{events} }}
  lines {{ name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 99 offset_ps: 10000000000
              duration_ps: 20000000000 }}
  }}
{metadata}
  event_metadata {{ key: 99 value {{ id: 99 name: "jit_train_step(1)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
'''
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert parts.main([str(path)]) == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed["ms"] == pytest.approx({
        "zoo.mixer": 3.0, "zoo.ffn": 4.5, "zoo.head": 6.0,
        "scoped rest": 1.5, "no scope": 1.0, "idle inside": 4.0,
        "program": 20.0})
    assert printed["no_scope"] == [["copy-done.7", pytest.approx(1.0)]]
