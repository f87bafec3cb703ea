"""Host time to make one step's input: the mean duration of the
``trainer.input`` spans (batch gather by index on the host, then
``device_put``) of the epochs between the edges. Hidden behind the device
while steps are queued; exposed on an epoch's first step. Reads the
program's phase spans (``harness/hostspans.py``); ``None`` when the program
records none or the ring has let the window's first epoch go."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh",)
CHIPS = None


def read(run):
    from harness import hostspans
    epochs = hostspans.by_epoch(hostspans.recorded())
    window = hostspans.window_epochs(run, epochs)
    if window is None:
        return None
    durations = [s["dur"] for e in window for s in epochs[e]["trainer.input"]]
    return 1e3 * sum(durations) / len(durations)
