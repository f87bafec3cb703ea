"""Host time of an epoch end: from the end of ``trainer.epoch_sync`` of one
epoch (the host knows the epoch's steps are done) to the start of the first
``trainer.step`` of the next, the stretch in which the host has no step
queued on the device. Mean over the epochs between the edges, each counted
with the epoch end that follows it; the last one's reaches a few
milliseconds past the last edge, and is left out if the next epoch had not
begun when this is read. Reads the program's phase spans
(``harness/hostspans.py``); ``None`` when the program records none or the
ring has let the window's first epoch go."""

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
    ends = [epochs[e + 1]["trainer.step"][0]["mono"]
            - hostspans.end(epochs[e]["trainer.epoch_sync"][0])
            for e in window if epochs.get(e + 1, {}).get("trainer.step")]
    if not ends:
        return None
    return 1e3 * sum(ends) / len(ends)
