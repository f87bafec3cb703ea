"""What every driver's session shares: the program's ``train()`` call on a
thread of its own, whose failure the main thread sees at its next look."""

from __future__ import annotations

import threading


class TrainerThread:
    """Runs ``train`` once, on a daemon thread that outlives the run."""

    def __init__(self, train, name: str):
        self._train = train
        self._error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    def _run(self) -> None:
        try:
            self._train()
        except BaseException as e:  # noqa: BLE001 — handed to the main thread
            self._error = e

    def check(self) -> None:
        if self._error is not None:
            raise RuntimeError("the trainer thread died") from self._error
