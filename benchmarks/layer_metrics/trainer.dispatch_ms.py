"""Host time to enqueue one step: the mean of ``dps_trainer_step_seconds``
between the edges. It is dispatch-to-return, not step time (the trainer's
own comment says so); it reaches the step period only when the runtime's
queue is full and the call blocks."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh",)
CHIPS = None


def read(run):
    first, last = run.edges
    if "dispatch_n" not in last or last["dispatch_n"] == first["dispatch_n"]:
        return None
    return 1e3 * run.delta("dispatch_sum_s") / run.delta("dispatch_n")
