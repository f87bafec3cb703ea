"""The compiled step's HLO text, kept at set-up, and the map from an ``XLA
Ops`` event to the named scope its instruction was traced under.

On the chip no event stat carries an instruction's ``op_name``: an event's
name is the HLO text without ``metadata={...}`` (PERF.md, section 3). The
compiled module's text has both, so a driver keeps it in a traced run
(:class:`KeptStep`) before its window opens, and a reader asks :func:`scope_seconds` for the device
time of a traced slice by scope.

A scope is a ``jax.named_scope`` (or a flax module's name) on the path in
``op_name``: ``jit(worker_step)/forward_backward/jvp(JoyAILM)/.../layer_1/
mla/attn/q_b/dot_general``. An instruction belongs to the FIRST name of
``order`` that its path holds, so ``order`` decides where nested scopes go
(``mtp`` before ``mla``: the MTP module's attention is the module's). XLA's
own expansions lose the path (a ragged dot becomes ``ragged-dot-none``
custom calls, a sort a bare ``sort``): ``by_instruction`` names those by
their instruction name. What neither finds is ``other``.
"""

from __future__ import annotations

import re

#: program name -> compiled HLO text, as the driver kept it
KEPT: dict = {}

#: the sync step's scopes (docs/OBSERVABILITY.md, "Named scopes"), in the
#: order of the rule above: the decoder LM's, then the step's own
#: (``forward_backward`` holds every model's pass and so comes last)
STEP_SCOPES = ("mtp", "mla", "moe_route", "moe_experts", "moe_shared",
               "dense_mlp", "head_loss", "embed", "update", "exchange",
               "augment", "forward_backward")
#: instructions whose path XLA's expansion lost, by name: the grouped
#: matmuls are ``ragged-dot-*`` custom calls; the step's only sort and
#: scatter are the expert layer's permutation and its way back
STEP_BY_INSTRUCTION = ((r"ragged-dot", "moe_experts"),
                       (r"(sort|scatter)", "moe_route"))

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
    re.M)


class KeptStep:
    """A jitted step in a traced run: compiled ahead of time at its first
    call, for that call's arguments, with the executable's text kept under
    ``name``; every call runs that executable. One compile (or one load
    from the persistent cache), and the text is of what ran."""

    def __init__(self, name: str, jitted):
        self._name, self._jitted, self._compiled = name, jitted, None

    def __call__(self, *args):
        if self._compiled is None:
            self._compiled = self._jitted.lower(*args).compile()
            KEPT[self._name] = self._compiled.as_text()
            print(f"[bench] kept the step's HLO text, "
                  f"{len(KEPT[self._name])} characters", flush=True)
        return self._compiled(*args)


def op_names(text: str) -> dict:
    """Instruction name -> ``op_name`` for every instruction that has one."""
    return {m.group(1): m.group(2) for m in _INSTRUCTION.finditer(text)}


def scope_of(instruction: str, op_name: str | None, order,
             by_instruction=()) -> str:
    parts = set(re.split(r"[/()]", op_name or ""))
    for scope in order:
        if scope in parts:
            return scope
    for pattern, scope in by_instruction:
        if re.match(pattern, instruction):
            return scope
    return "other"


def scope_seconds(run, program: str, order, by_instruction=()):
    """``({scope: device seconds a step}, {scope: {instruction kind:
    seconds a step}})`` over device 0's step runs in the traced slice:
    each ``XLA Ops`` event inside a step run, less the events nested in it,
    charged to its instruction's scope. ``None`` without a trace or without
    the program's text."""
    if run.trace is None or program not in KEPT:
        return None
    from . import xplane
    names = op_names(KEPT[program])
    d = run.trace.devices[0]
    inside = [(xplane.op_name(n), s, e) for (n, s, e) in d.ops
              if any(lo <= s and e <= hi for (_m, lo, hi) in d.steps)]
    scopes: dict = {}
    kinds: dict = {}
    for instruction, seconds in xplane.self_times(inside).items():
        scope = scope_of(instruction, names.get(instruction), order,
                         by_instruction)
        seconds /= len(d.steps)
        scopes[scope] = scopes.get(scope, 0.0) + seconds
        kind = re.sub(r"\.\d+$", "", instruction)
        table = kinds.setdefault(scope, {})
        table[kind] = table.get(kind, 0.0) + seconds
    return scopes, kinds


def step_scope_ms(run, scope: str):
    """Device milliseconds a step under ``scope`` of ``STEP_SCOPES`` in the
    kept ``jit_worker_step``, forward and backward; ``None`` when there is
    nothing to read. The first reader of a run logs the whole table, and
    what the catch-all rows (``forward_backward``: under no inner scope;
    ``other``: no path at all) are made of, by instruction kind."""
    if "step_scopes" not in run.__dict__:
        run.step_scopes = scope_seconds(run, "jit_worker_step", STEP_SCOPES,
                                        STEP_BY_INSTRUCTION)
        if run.step_scopes is not None:
            scopes, kinds = run.step_scopes
            rows = sorted(scopes.items(), key=lambda kv: -kv[1])
            print(f"[bench] device ms a step by scope (median step "
                  f"{run.trace.step_device_ms():.3f}): "
                  + ", ".join(f"{name} {1e3 * seconds:.3f}"
                              for name, seconds in rows)
                  + f"; sum {1e3 * sum(scopes.values()):.3f}", flush=True)
            for rest in ("forward_backward", "other"):
                top = sorted(kinds.get(rest, {}).items(),
                             key=lambda kv: -kv[1])[:8]
                print(f"[bench] {rest} is: " + ", ".join(
                    f"{kind} {1e3 * seconds:.3f}" for kind, seconds in top),
                    flush=True)
    if run.step_scopes is None:
        return None
    scopes, _kinds = run.step_scopes
    return 1e3 * scopes[scope] if scope in scopes else None


def kernel_roofline(run, kernel: str):
    """Percent of its roofline at which the Pallas kernel ``kernel`` ran in
    the traced slice: the least time the chip could take for the calls made
    (each call's operations at the bf16 peak or its bytes at the HBM
    bandwidth, whichever is longer; both from the configuration's
    ``ops_count`` file, function ``<kernel>_cost(architecture, batch)``)
    over the device time of the kernel's events. ``None`` without a trace
    or when the slice holds no such event."""
    if run.trace is None:
        return None
    from . import xplane
    d = run.trace.devices[0]
    lo, hi = d.window
    events = [(s, e) for (n, s, e) in d.ops
              if xplane.op_kind(n) == kernel and s >= lo and e <= hi]
    cost = getattr(run.cell._ops_count(), kernel + "_cost", None)
    if not events or cost is None:
        return None
    operations, nbytes = cost(run.cell.config["architecture"],
                              run.images_per_device_step)
    by_compute = operations / run.peak["bf16_flops_per_s"]
    by_memory = nbytes / run.peak["hbm_bytes_per_s"]
    seconds = sum(e - s for s, e in events) / 1e9
    print(f"[bench] {kernel}: {len(events)} calls, {1e3 * seconds:.3f} ms; "
          f"a call needs {operations / 1e9:.1f} GFLOP "
          f"({1e3 * by_compute:.3f} ms at peak) and {nbytes / 1e6:.1f} MB "
          f"({1e3 * by_memory:.3f} ms), bound by "
          f"{'compute' if by_compute >= by_memory else 'memory'}",
          flush=True)
    return 100.0 * len(events) * max(by_compute, by_memory) / seconds
