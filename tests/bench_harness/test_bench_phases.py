"""What a capture says of an operation, and the model step by phase: ``load``
on a hand-built capture (scopes from the metadata's stats and, where those
leave one out, from the capture's own copy of the program; a loop and its
children; an operation with no scope), the scope by instruction name on a
compiled ``jax.checkpoint`` toy, the phase account on hand-built events,
and each reader that this brought into ``BENCHMARK.json`` on a hand-built
run, silent where the capture has no device plane."""

import os

import pytest

from benchmark import xplane
from benchmark.manifest import Manifest, load_module
from benchmark.xplane import Capture, Event

MS = 1e6        # a device event's nanoseconds, a millisecond
PLANE = "/device:TPU:0"
STEP = "jit(train_step)/"
FWD, BWD = STEP + "jvp()/", STEP + "transpose(jvp(jvp()))/checkpoint/"
AGAIN = BWD + "rematted_computation/"
PHASE_READERS = {
    "step_device_ms": "program", "step_forward_ms": "forward",
    "step_backward_ms": "backward", "step_made_again_ms": "made again",
    "step_optimizer_ms": "optimizer"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def phases(manifest):
    return load_module(os.path.join(manifest.home, "layer_metrics",
                                    "_phases.py"))


# -- the capture ----------------------------------------------------------

def _message(*fields) -> bytes:
    """A protobuf message from (field number, bytes or int) pairs."""
    def varint(n):
        out = b""
        while n > 0x7F:
            out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
        return out + bytes([n])

    return b"".join(
        varint(number << 3 | 2) + varint(len(value)) + value
        if isinstance(value, bytes) else varint(number << 3) + varint(value)
        for number, value in fields)


def _hlo_proto(instructions: dict) -> bytes:
    """An ``HloProto`` whose module holds one computation of
    ``instructions`` {name: ``op_name``}."""
    return _message((1, _message(
        (1, b"jit_train_step"), (3, _message(
            (1, b"region_0.1"), *(
                (2, _message((1, name.encode()), (2, b"fusion"),
                             (7, _message((1, b"op"),
                                          (2, scope.encode())))))
                for name, scope in instructions.items()))))))


def _escaped(raw: bytes) -> str:
    return "".join(f"\\{byte:03o}" for byte in raw)


def _capture_text() -> str:
    """Two step programs of 10 ms.  In each: a forward fusion, a loop whose
    stats leave its scope out (the program's copy says it), inside it a
    backward kernel and an operation made again, and a copy that the
    compiler put in, with no scope anywhere."""
    said = [   # display name, HLO line's rest, tf_op, category
        ("fusion.1", "f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
         FWD + "mul:", "loop fusion"),
        ("while.3", "(s32[]) while((s32[]) %t), condition=%c, body=%b",
         "", "while"),
        ("_flash_bwd_kernel.13", "(bf16[8]{0}, bf16[8]{0}) custom-call("
         'bf16[8]{0} %q), custom_call_target="tpu_custom_call"',
         BWD + "jit(_flash_bwd_kernel)/pallas_call:", "custom-call"),
        ("fusion.7", "f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput",
         AGAIN + "dot_general:", "convolution fusion"),
        ("copy-done.2", "f32[8]{0} copy-done((f32[8]{0}) %copy-start.2)",
         "", "copy-done")]
    metadata = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} '
        f'name: "%{name} = {rest.replace(chr(34), chr(92) + chr(34))}" '
        f'display_name: "{name}" '
        + (f'stats {{ metadata_id: 1 str_value: "{scope}" }} '
           if scope else "")
        + f'stats {{ metadata_id: 2 str_value: "{category}" }} }} }}\n'
        for i, (name, rest, scope, category) in enumerate(said, 1))
    ops = "".join(
        f"events {{ metadata_id: {i} offset_ps: {int((t + at) * 1e9)} "
        f"duration_ps: {int(dur * 1e9)} }}\n"
        for t in (10, 30) for i, at, dur in (
            (1, 0, 2), (2, 2, 6), (3, 3, 2), (4, 5, 2.5), (5, 8.5, 1)))
    program = _escaped(_hlo_proto({"while.3": BWD + "while",
                                   "other.9": FWD + "add"}))
    return f'''
planes {{
  name: "{PLANE}"
  lines {{ name: "XLA Ops" timestamp_ns: 1000
{ops} }}
  lines {{ name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 9 offset_ps: 10000000000 duration_ps: 10000000000 }}
    events {{ metadata_id: 9 offset_ps: 30000000000 duration_ps: 10000000000 }}
    events {{ metadata_id: 8 offset_ps: 50000000000 duration_ps: 1000000000 }}
  }}
{metadata}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit_train_step(123)" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "jit_convert(7)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "hlo_category" }} }}
}}
planes {{
  name: "/host:metadata"
  event_metadata {{ key: 123 value {{ id: 123 name: "jit_train_step(123)"
    stats {{ metadata_id: 1 bytes_value: "{program}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
planes {{
  name: "Task Environment"
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }}
  stats {{ metadata_id: 1 uint64_value: 1791187968476714034 }}
}}
'''


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("capture") / "hand.xplane.pb"
    path.write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_capture_text()))
    return xplane.load(str(path))


def test_load_keeps_what_the_trace_says_of_an_operation(capture):
    assert capture.start_ns == 1791187968476714034
    assert [(m.name, m.start_ns, m.dur_ns) for m in capture.modules[PLANE]] \
        == [("jit_train_step", 10 * MS + 1000, 10 * MS),
            ("jit_train_step", 30 * MS + 1000, 10 * MS),
            ("jit_convert", 50 * MS + 1000, 1 * MS)]
    ops = capture.device_ops[PLANE]
    assert len(ops) == 10
    fusion, loop, kernel, again, copy = ops[:5]
    # the existing fields as they were: the short name, start, duration
    assert (fusion.name, fusion.start_ns, fusion.dur_ns) \
        == ("fusion.1", 10 * MS + 1000, 2 * MS)
    assert kernel.name == "_flash_bwd_kernel.13/tpu_custom_call/2"
    # the scope: the metadata's stat, without the colon that ends it
    assert (fusion.scope, fusion.category) == (FWD + "mul", "loop fusion")
    assert kernel.scope == BWD + "jit(_flash_bwd_kernel)/pallas_call"
    assert again.scope == AGAIN + "dot_general"
    # the loop's stats say none: the capture's copy of the program does
    assert (loop.scope, loop.category) == (BWD + "while", "while")
    # the compiler's copy has none anywhere
    assert (copy.name, copy.scope, copy.category) \
        == ("copy-done.2", "", "copy-done")


def test_a_loop_s_children_are_inside_it_and_not_beside_it(capture):
    ops = capture.device_ops[PLANE]
    assert xplane.nest(ops) == [-1, -1, 1, 1, -1, -1, -1, 6, 6, -1]
    by_name = xplane.op_seconds(ops)
    assert by_name == pytest.approx({"fusion.1": 0.004, "while.3": 0.012,
                                     "copy-done.2": 0.002})
    assert xplane.top(by_name, 1) == [["while.3", pytest.approx(0.012)]]
    # every busy nanosecond once, to the innermost operation open then
    pieces = [(ops[i].name.split("/")[0], round((b - a) / MS, 3))
              for i, a, b in xplane.owned(ops[:5])]
    assert pieces == [("fusion.1", 2), ("while.3", 1),
                      ("_flash_bwd_kernel.13", 2), ("fusion.7", 2.5),
                      ("while.3", 0.5), ("copy-done.2", 1)]
    assert sum(b - a for _i, a, b in xplane.owned(ops)) / 1e9 \
        == pytest.approx(xplane.busy_seconds(ops))


def test_operations_that_overlap_are_not_taken_for_a_loop_s():
    ops = [Event("fusion.1", 10 * MS, 8 * MS),
           Event("convolution.2", 16 * MS, 10 * MS),   # overlaps, no loop
           Event("while.4", 30 * MS, 10 * MS),
           Event("fusion.5", 31 * MS, 2 * MS),
           Event("while.6", 33 * MS, 6 * MS),          # a loop in a loop
           Event("add.7", 34 * MS, 1 * MS),
           Event("fusion.8", 40 * MS, 1 * MS)]         # after the loop
    assert xplane.nest(ops) == [-1, -1, -1, 2, 2, 4, -1]
    assert [(ops[i].name, a / MS, b / MS) for i, a, b in xplane.owned(ops)] \
        == [("fusion.1", 10, 16), ("convolution.2", 16, 26),
            ("while.4", 30, 31), ("fusion.5", 31, 33), ("while.6", 33, 34),
            ("add.7", 34, 35), ("while.6", 35, 39), ("while.4", 39, 40),
            ("fusion.8", 40, 41)]


def test_the_scope_by_instruction_name_on_a_compiled_toy(phases):
    """Two checkpointed layers under ``value_and_grad`` with an SGD
    update, compiled here: the compiled module's instructions carry the
    forward pass, the backward pass, what it makes again and the update in
    their ``op_name``, and the rule tells the four apart."""
    import jax
    import jax.numpy as jnp

    def layer(w, x):
        return jnp.tanh(x @ w)

    def loss(params, x):
        for w in params:
            x = jax.checkpoint(layer)(w, x)
        return jnp.mean(x ** 2)

    @jax.jit
    def train_step(params, x):
        value, grads = jax.value_and_grad(loss)(params, x)
        return [w - 0.1 * g for w, g in zip(params, grads)], value

    params = [jnp.full((64, 64), 0.01) for _ in range(2)]
    compiled = train_step.lower(params, jnp.ones((8, 64))).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    scopes = xplane.module_scopes(module.as_serialized_hlo_module_proto())
    # the names are the compiled text's
    text = compiled.as_text()
    assert all(f"{name} = " in text for name in scopes)
    found = {}
    for name, scope in scopes.items():
        if scope.startswith(STEP):
            found.setdefault(phases.phase_of(scope), set()).add(
                scope.rsplit("/", 1)[1])
    assert "dot_general" in found[phases.FORWARD]
    assert "dot_general" in found[phases.MADE_AGAIN]
    assert "tanh" in found[phases.MADE_AGAIN]
    assert {"dot_general", "transpose"} & found[phases.BACKWARD]
    assert "sub" in found[phases.OPTIMIZER]
    assert phases.NO_SCOPE not in found


# -- the step by phase ------------------------------------------------------

def test_the_rule_that_classes_an_operation(phases):
    assert phases.phase_of(FWD + "dot_general") == phases.FORWARD
    assert phases.phase_of(
        STEP + "jvp(jit(_flash_fwd_pallas))/pallas_call") == phases.FORWARD
    assert phases.phase_of(BWD + "while/body/jit(gmm)/pallas_call") \
        == phases.BACKWARD
    assert phases.phase_of(STEP + "transpose(jvp())/mul") == phases.BACKWARD
    assert phases.phase_of(AGAIN + "tanh") == phases.MADE_AGAIN
    assert phases.phase_of(STEP + "sub") == phases.OPTIMIZER
    # a fusion of two: made again before backward before forward
    assert phases.phase_of(FWD + "mul;" + AGAIN + "mul") == phases.MADE_AGAIN
    assert phases.phase_of("") == phases.NO_SCOPE


def test_the_phases_and_the_idle_inside_add_up_to_the_program(phases):
    programs = [Event("jit_train_step", t * MS, 20 * MS) for t in (10, 40)]
    ops = []
    for t in (10, 40):
        ops += [
            Event("fusion.1", t * MS, 3 * MS, FWD + "dot_general"),
            # a forward loop of 6 ms: 4 of its children's, 2 of its own
            Event("while.2", (t + 3) * MS, 6 * MS, FWD + "while"),
            Event("fusion.3", (t + 4) * MS, 2 * MS, FWD + "while/body/mul"),
            Event("fusion.3", (t + 6) * MS, 2 * MS, FWD + "while/body/mul"),
            # 1 ms of nothing, then the backward pass and what it makes again
            Event("fusion.4", (t + 10) * MS, 2 * MS, AGAIN + "dot_general"),
            Event("fusion.5", (t + 12) * MS, 4 * MS, BWD + "dot_general"),
            Event("copy-done.6", (t + 16) * MS, 0.5 * MS),
            Event("fusion.7", (t + 16.5) * MS, 1.5 * MS, STEP + "sub"),
            # 2 ms idle at the program's end
        ]
    # an operation outside every step program is no phase's
    ops.append(Event("fusion.9", 70 * MS, 5 * MS, FWD + "convert"))
    by_phase, unnamed = phases.account(ops, programs)
    assert by_phase == pytest.approx({
        phases.FORWARD: 18 * MS, phases.MADE_AGAIN: 4 * MS,
        phases.BACKWARD: 8 * MS, phases.OPTIMIZER: 3 * MS,
        phases.NO_SCOPE: 1 * MS, phases.IDLE: 6 * MS,
        phases.PROGRAM: 40 * MS})
    assert unnamed == pytest.approx({"copy-done.6": 1 * MS})
    assert sum(by_phase[p] for p in phases.PHASES) + by_phase[phases.IDLE] \
        == pytest.approx(by_phase[phases.PROGRAM])
    # a loop's own time is its span less its children's, in its own phase:
    # a backward child of a forward loop takes its time out of the forward
    ops[2] = Event("fusion.3", 14 * MS, 2 * MS, BWD + "mul")
    by_phase, _ = phases.account(ops, programs)
    assert by_phase[phases.FORWARD] == pytest.approx(16 * MS)
    assert by_phase[phases.BACKWARD] == pytest.approx(10 * MS)


def _phase_run(capture, **more):
    programs = {plane: [m for m in modules
                        if m.name.startswith("jit_train_step")]
                for plane, modules in capture.modules.items()}
    return {"capture": capture, "step_modules": programs, **more}


@pytest.mark.parametrize("metric", sorted(PHASE_READERS))
def test_a_phase_reader_on_the_hand_built_capture(manifest, capture, metric):
    want = {"program": 10.0, "forward": 2.0, "backward": 3.5,
            "made again": 2.5, "optimizer": 0.0}[PHASE_READERS[metric]]
    read = manifest.reader(metric)
    run = _phase_run(capture)
    assert read(run) == pytest.approx(want)
    # the account is made once a run and names what it could not class
    assert run["step_phases"]["unnamed"] == pytest.approx(
        {"copy-done.2": 2 * MS})
    assert run["step_phases"]["ns"]["idle inside"] == pytest.approx(2 * MS)
    # two chips, each with these programs: the same mean
    both = Capture({PLANE: capture.device_ops[PLANE],
                    "/device:TPU:1": capture.device_ops[PLANE]},
                   {PLANE: capture.modules[PLANE],
                    "/device:TPU:1": capture.modules[PLANE]})
    assert read(_phase_run(both)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(PHASE_READERS))
def test_a_phase_reader_is_silent_without_a_device_plane(manifest, capture,
                                                         metric):
    read = manifest.reader(metric)
    assert read({"capture": None, "step_modules": {}}) is None
    assert read(_phase_run(Capture({}, {}))) is None      # the CPU's capture
    # a device plane without a step program on it
    assert read({"capture": capture, "step_modules": {PLANE: []}}) is None


def test_step_lower_is_the_lowering_s_seconds_at_the_end_of_set_up(manifest):
    read = manifest.reader("step_lower_s")
    run = {"registry_before": {
        ("zoo_lower_seconds", "train_step"): (2.5, 1),
        ("zoo_lower_seconds", "eval_step"): (0.75, 1),
        ("zoo_compile_seconds", "train_step"): (40.0, 1)},
        "registry_after": {("zoo_lower_seconds", "train_step"): (9.0, 2)}}
    assert read(run) == pytest.approx(3.25)
    # a program without the family (the commits before PR 27): no number
    assert read({"registry_before": {
        ("zoo_compile_seconds", "train_step"): (40.0, 1)}}) is None


def test_every_appended_entry_has_its_keys(manifest):
    entries = {m["name"]: m for m in manifest.doc["per_layer"]}
    names = list(entries)
    appended = ["step_device_ms", "step_forward_ms", "step_backward_ms",
                "step_made_again_ms", "step_optimizer_ms", "step_lower_s",
                "mla_attention_roofline", "expert_matmul_roofline",
                "kda_scan_roofline"]
    assert names[names.index("step_device_ms"):][:len(appended)] == appended
    for name in appended[:5]:
        assert entries[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": "train_examples_per_s"}
    assert entries["step_lower_s"]["moves"] == "setup_s"
    routed = ["kanana-2-30b-a3b-fit", "kimi-linear-48b-a3b-fit"]
    assert entries["mla_attention_roofline"]["workloads"] == routed
    assert entries["expert_matmul_roofline"]["workloads"] == routed
    assert entries["kda_scan_roofline"]["workloads"] == routed[1:]
    assert entries["flash_attention_roofline"]["workloads"] \
        == ["gpt2-small-fit", "ouro-2.6b-fit"]


# -- the idle gaps by the host's span ---------------------------------------

def test_a_gap_between_programs_is_named_by_the_span_open_then(manifest):
    spans = load_module(os.path.join(manifest.home, "layer_metrics",
                                     "_spans.py"))
    # two calls of two steps; 5 ms between steps, 15 ms between the calls
    modules = [Event("jit_train_step", t * MS, 20 * MS)
               for t in (10, 35, 70, 95)]
    ops = [Event("fusion.1", m.start_ns, 19 * MS) for m in modules]
    on_device = [(54 * MS, 62 * MS, "zoo.train.epoch_sync"),
                 (62 * MS, 75 * MS, "zoo.train.data_wait"),
                 (28 * MS, 200 * MS, "zoo.train.epoch")]
    by_place = xplane.idle_by_place(
        ops, modules, 2, 0.120,
        lambda gaps: spans.idle_by_span(gaps, on_device))
    assert by_place == pytest.approx({
        xplane.INSIDE: 0.004, xplane.EDGES: 0.015,
        "between_steps/zoo.train.epoch": 0.010,
        "between_calls/zoo.train.epoch_sync": 0.007,
        "between_calls/zoo.train.data_wait": 0.008})
    # where the clocks cannot be paired the four places stand as they did
    assert xplane.idle_by_place(ops, modules, 2, 0.120) == pytest.approx({
        xplane.INSIDE: 0.004, xplane.EDGES: 0.015,
        xplane.BETWEEN_STEPS: 0.010, xplane.BETWEEN_CALLS: 0.015})


# -- the experts' count by either name ---------------------------------------

def test_expert_matmul_roofline_where_the_file_says_num_experts(manifest):
    """``kimi-linear-48b-a3b``'s file names the held experts
    ``num_experts`` where kanana's says ``n_routed_experts``."""
    experts = load_module(os.path.join(manifest.home, "layer_metrics",
                                       "expert_matmul_roofline.py"))
    cfg = manifest.configuration("kimi-linear-48b-a3b")
    assert "n_routed_experts" not in cfg.sizes
    peaks = manifest.peaks("TPU v5 lite")

    def least(flops, nbytes):
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    product = experts.call_costs(2000.0, 2304, 1024, 8)
    assert product[1] == 2 * (2000 * (2304 + 1024) + 8 * 2304 * 1024)
    summed = experts.sum_costs(2000.0, 2304, 8192)

    def kernel(name, start, seconds):
        line = (f"%{name} = bf16[8192,1024]{{1,0}} custom-call(bf16[8]{{0}} "
                '%q), custom_call_target="tpu_custom_call"')
        return Event(xplane.short_name(line), start, seconds * 1e9)

    # one window of one layer: every call at half of its roofline
    ops = [kernel(f"gmm.{k}", k * 1e7, 2 * least(*product))
           for k in range(8)]
    ops += [kernel(f"tgmm.{k}", (8 + k) * 1e7, 2 * least(*product))
            for k in range(3)]
    ops += [kernel(f"tgmm.{k}", (8 + k) * 1e7, 2 * least(*summed))
            for k in range(3, 5)]
    run = {"capture": Capture({PLANE: ops}, {}), "sizes": cfg.sizes,
           "configuration": cfg, "manifest": manifest,
           "traffic": {"batch": 2}, "device": {"kind": "TPU v5 lite"},
           "registry_after": {("zoo_moe_held_assignments", "2"): (2000.0, 1)}}
    assert experts.read(run) == pytest.approx(50.0)
