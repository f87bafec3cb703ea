"""The host's side of a run: the sync trainer's phase spans, and the join
of their clock to a device trace's.

``SyncTrainer.train`` records one ``trainer.epoch`` span a pass of its
epoch loop and one child a phase (``PHASES``) into the program's flight
recorder in every run (``telemetry/trace.py``, ``always=True``), each with
its start on ``time.monotonic()`` (``mono``), the clock of the window's
edges. A program that records no such spans (an older commit) gives the
readers nothing: they return ``None`` and the line leaves the metric out.

The device trace has a clock of its own (ns from the profile's start), and
the harness keeps nothing that says when that was. :func:`join_clocks`
finds ``offset`` with host time = device time + ``offset`` from orderings
the program guarantees:

- a wait returns after the device run it waits for has ended:
  ``trainer.epoch_sync``'s ``block_until_ready`` returns (``ready_mono``)
  after the epoch's last step run, and ``trainer.eval`` ends after its
  epoch's last ``jit_eval_step`` run, so each gives
  ``offset <= host - device``;
- a run starts after the host began to dispatch it: a step's module run
  starts after its ``trainer.step`` span did, the evaluation's after
  ``trainer.eval`` did, so each gives ``offset >= host - device``.

Which run is which: the profiler is stopped right after the last edge, so
the last evaluation in the trace is that of the edge's epoch, and
``steps_per_epoch`` step runs lie between two evaluations.

The two sides are not equally tight (TPU v5e, PR 26's chip runs). A wait
trails the device by the runtime's notification alone, and the waits of one
trace's epochs agree within 0.15 ms. A run that starts on an idle device
waits for its input's transfer too, not only for its launch: 5 ms for an
evaluation batch, 14-19 ms for a training batch. So the estimate is the
interval's upper end, the tightest wait; ``width_s`` says how far below it
the orderings would let the true offset lie, and ``agreement_s`` how far
the epochs' tightest waits lie apart, which is the estimate's precision.
"""

from __future__ import annotations

from dataclasses import dataclass

from . import xplane

#: the children of ``trainer.epoch``, in the order an epoch runs them
PHASES = ("trainer.input", "trainer.step", "trainer.epoch_sync",
          "trainer.eval", "trainer.epoch_report", "trainer.checkpoint")
EPOCH = "trainer.epoch"
EVAL_MODULE = "jit_eval_step"
#: the last evaluation ends at most this long before the last edge is
#: stamped: its count's fetch, and the driver's 2 ms poll
EDGE_SLACK_S = 0.005


def end(span: dict) -> float:
    return span["mono"] + span["dur"]


def recorded() -> list[dict]:
    """The trainer's spans the program's recorder holds, oldest first."""
    try:
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            import get_recorder
    except ImportError:
        return []
    return [s for s in get_recorder().tail()
            if s.get("name") == EPOCH or s.get("name") in PHASES]


def by_epoch(spans) -> dict:
    """``{epoch: {name: [span, ...]}}``, each list ordered by start. Spans
    without ``mono`` or an ``epoch`` attribute (a program from before they
    had them) are left out."""
    out: dict = {}
    for s in spans:
        epoch = s.get("attrs", {}).get("epoch")
        if epoch is None or "mono" not in s:
            continue
        out.setdefault(epoch, {}).setdefault(s["name"], []).append(s)
    for phases in out.values():
        for group in phases.values():
            group.sort(key=lambda s: s["mono"])
    return out


def window_epochs(run, epochs: dict) -> list[int] | None:
    """The epochs (the spans' 0-based ``epoch``) between the run's edges,
    or ``None`` when the recorder no longer holds them whole: every step of
    each, and its ``trainer.epoch_sync``."""
    first, last = run.edges
    if "epochs" not in first or last["epochs"] <= first["epochs"]:
        return None
    window = list(range(first["epochs"], last["epochs"]))
    steps = (last["steps"] - first["steps"]) // len(window)
    for e in window:
        phases = epochs.get(e, {})
        if len(phases.get("trainer.step", [])) != steps \
                or len(phases.get("trainer.input", [])) != steps \
                or not phases.get("trainer.epoch_sync"):
            return None
    return window


@dataclass
class ClockJoin:
    offset_s: float      # host monotonic seconds = device seconds + offset
    width_s: float       # of the interval the orderings leave below it
    agreement_s: float   # the epochs' tightest waits lie this far apart

    def to_host(self, device_ns: float) -> float:
        return device_ns / 1e9 + self.offset_s


def _blocks(runs) -> list[list]:
    """``runs`` (ordered by start) cut wherever they change between the step
    module and the evaluation: ``[steps, evals, steps, ..., evals, steps]``,
    first and last of which may be empty."""
    blocks: list[list] = [[]]
    for run in runs:
        if (xplane.module_name(run[0]) == EVAL_MODULE) != (
                len(blocks) % 2 == 0):
            blocks.append([])
        blocks[-1].append(run)
    if len(blocks) % 2 == 0:
        blocks.append([])
    return blocks


def join_clocks(device, step_module: str, epochs: dict, last_edge: dict,
                steps_per_epoch: int, log=print) -> ClockJoin | None:
    """The offset between ``device``'s clock (an ``xplane.DeviceReduction``)
    and the spans' (``epochs`` from :func:`by_epoch`), or ``None``, with the
    reason given to ``log``, when a check fails: (a) the orderings leave an
    interval; (b) ``steps_per_epoch`` step runs lie between evaluations;
    (c) the last evaluation's end, mapped, lies within ``EDGE_SLACK_S``
    before the last edge."""
    def no(reason: str) -> None:
        log(f"[bench] clock join: {reason}")

    blocks = _blocks(sorted(
        (m for m in device.modules
         if xplane.module_name(m[0]) in (step_module, EVAL_MODULE)),
        key=lambda m: m[1]))
    if len(blocks) < 3:
        return no("the trace holds no run of " + EVAL_MODULE)
    lower = []           # bounds on the offset, seconds
    upper = []           # each epoch's tightest, newest epoch first

    def starts_after(run, span) -> None:
        lower.append(span["mono"] - run[1] / 1e9)

    # Newest first: the last evaluation is the edge's epoch's, the step
    # runs before an evaluation are its epoch's, those after the last one
    # the first of the next epoch's.
    epoch = last_edge["epochs"] - 1
    tail = blocks[-1]
    for run, span in zip(tail, epochs.get(epoch + 1, {}).get(
            "trainer.step", [])):
        starts_after(run, span)
    if len(tail) > steps_per_epoch:
        return no(f"{len(tail)} step runs after the last evaluation, more "
                  f"than an epoch's {steps_per_epoch}")
    for i in range(len(blocks) - 2, 0, -2):
        steps, evals = blocks[i - 1], blocks[i]
        phases = epochs.get(epoch, {})
        spans = phases.get("trainer.step", [])
        if not phases.get("trainer.eval") or len(spans) != steps_per_epoch:
            return no(f"the recorder holds epoch {epoch} no longer")
        evaluation = phases["trainer.eval"][0]
        whole = i > 1                # an evaluation lies before these steps
        if len(steps) > steps_per_epoch \
                or whole and len(steps) != steps_per_epoch:    # check (b)
            return no(f"epoch {epoch}: {len(steps)} step runs before its "
                      f"evaluation, not {steps_per_epoch}")
        if (whole or steps) and len(evals) != int(
                evaluation["attrs"].get("batches", 1)):
            return no(f"epoch {epoch}: {len(evals)} runs of {EVAL_MODULE}, "
                      f"not the evaluation's batches")
        if whole or steps:           # else the trace begins inside it
            starts_after(evals[0], evaluation)
        waits = [end(evaluation) - evals[-1][2] / 1e9]
        for run, span in zip(steps, spans[-len(steps):]):
            starts_after(run, span)
        ready = phases.get("trainer.epoch_sync", [{}])[0].get(
            "attrs", {}).get("ready_mono")
        if steps and ready is not None:
            waits.append(ready - steps[-1][2] / 1e9)
        upper.append(min(waits))
        epoch -= 1
    if not lower:
        return no("the trace begins inside its only evaluation")
    lo, hi = max(lower), min(upper)
    if lo > hi:                                          # check (a)
        return no(f"the orderings contradict each other by "
                  f"{(lo - hi) * 1e3:.3f} ms")
    join = ClockJoin(offset_s=hi, width_s=hi - lo,
                     agreement_s=max(upper) - hi)
    before_edge = last_edge["t"] - join.to_host(blocks[-2][-1][2])
    if not 0.0 <= before_edge <= EDGE_SLACK_S:           # check (c)
        return no(f"the last evaluation ends {before_edge * 1e3:.3f} ms "
                  f"before the last edge, not within "
                  f"{EDGE_SLACK_S * 1e3:.0f} ms")
    log(f"[bench] clock join: host = device + {join.offset_s:.6f} s, the "
        f"tightest of {len(upper)} epochs' waits, which agree within "
        f"{join.agreement_s * 1e3:.3f} ms; the {len(lower)} starts would "
        f"allow {join.width_s * 1e3:.3f} ms less; the last evaluation ends "
        f"{before_edge * 1e3:.3f} ms before the last edge")
    return join


def join_run(run, epochs: dict, log=print) -> ClockJoin | None:
    """:func:`join_clocks` on device 0 of a traced run."""
    first, last = run.edges
    if run.trace is None or "epochs" not in last \
            or last["epochs"] <= first["epochs"]:
        return None
    steps = (last["steps"] - first["steps"]) // (
        last["epochs"] - first["epochs"])
    return join_clocks(run.trace.devices[0], run.trace.step_module, epochs,
                       last, steps, log)
