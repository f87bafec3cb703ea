"""Of device 0's idle time in the traced window, in gaps of 20 us or more,
the share that falls inside one of the program's named phase spans
(``trainer.input`` .. ``trainer.checkpoint``: not ``trainer.epoch``'s self
time, and not time outside every epoch), after the host's clock is joined
to the trace's (``harness/hostspans.py``). It also prints the idle seconds
by phase span, for the gaps before each program the device ran next: what
the host was doing while the device waited. ``None`` without a trace,
without spans, or when the clocks cannot be joined (the reason is then in
the log)."""

LAYER = "device"
UNIT = "fraction"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh",)
CHIPS = None

SELF = "trainer.epoch (self)"
OUTSIDE = "outside every epoch"


def idle_by_phase(gaps_s, spans) -> dict:
    """Seconds of the idle intervals ``gaps_s`` (host clock) by the name of
    the phase span they fall in; what falls in a ``trainer.epoch`` span and
    in none of its children is ``SELF``, the rest ``OUTSIDE``."""
    from harness import hostspans, xplane
    table = {}
    for lo, hi in gaps_s:
        inside = {}
        for s in spans:
            start, end = max(s["mono"], lo), min(hostspans.end(s), hi)
            if end > start:
                inside.setdefault(s["name"], []).append((start, end))
        seconds = {name: xplane.total(xplane.union(parts))
                   for name, parts in inside.items()}
        in_epoch = seconds.pop(hostspans.EPOCH, 0.0)
        named = sum(seconds.values())
        seconds[SELF] = max(0.0, in_epoch - named)
        seconds[OUTSIDE] = max(0.0, (hi - lo) - max(in_epoch, named))
        for name, value in seconds.items():
            table[name] = table.get(name, 0.0) + value
    return table


def read(run):
    if run.trace is None:
        return None
    from harness import hostspans, xplane
    spans = hostspans.recorded()
    join = hostspans.join_run(run, hostspans.by_epoch(spans))
    if join is None:
        return None
    d = run.trace.devices[0]
    by_next: dict = {}       # the program the device ran next -> its gaps
    for a, b in xplane.gaps(d.covered, *d.window):
        if b - a >= xplane.SHORT_GAP_NS:
            by_next.setdefault(xplane.attribute_gap(a, b, d.modules),
                               []).append((join.to_host(a), join.to_host(b)))
    idle = named = 0.0
    for name, gaps_s in sorted(by_next.items()):
        table = idle_by_phase(gaps_s, spans)
        seconds = sum(table.values())
        idle += seconds
        named += seconds - table[SELF] - table[OUTSIDE]
        rows = sorted(table.items(), key=lambda kv: -kv[1])
        print(f"[bench] idle on device 0 {name}: {len(gaps_s)} gaps of 20 "
              f"us or more, {seconds:.6f} s, by the host's phase span: "
              + ", ".join(f"{phase} {value:.6f}" for phase, value in rows
                          if value > 0.0), flush=True)
    if idle <= 0.0:
        return None
    return named / idle
