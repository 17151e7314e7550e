"""Model step, whole: the operations the forward and backward passes of
the window's examples need (the configuration's ``ops.py``, from its
shapes; recomputation not counted) over the time the device spent inside
the step programs, which the trace's ``XLA Modules`` line gives, and the
chips' bf16 peak.  The waits between steps and between calls are not in
it: they are the device layer's (``device_idle_pct``)."""


def read(run):
    step_modules = run["step_modules"]
    if not step_modules or not all(step_modules.values()):
        return None
    steps = run["window"]["steps"]
    for plane, modules in step_modules.items():
        # one program a step, or one for every k steps of a dispatch: a
        # capture that lost programs would read too high
        if steps % len(modules):
            raise ValueError(f"{len(modules)} step programs on {plane} "
                             f"for the window's {steps} steps")
    peaks = run["manifest"].peaks(run["device"]["kind"])
    ops = run["configuration"].module("ops")
    flops = ops.train_flops_per_example(run["sizes"]) \
        * run["window"]["examples"]
    # every chip runs its share of every step: the chips' seconds add up
    seconds = sum(e.dur_ns for steps in step_modules.values()
                  for e in steps) / 1e9
    return 100.0 * flops / (seconds * peaks["bf16_flops_per_s"])
