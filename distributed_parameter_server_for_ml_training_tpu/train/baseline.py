"""Single-machine baseline trainer (reference: baseline/baseline_training.py).

Same recipe — ResNet-18/CIFAR-100, batch 128, SGD(momentum 0.9, wd 5e-4),
MultiStepLR([10,15], gamma 0.1), per-epoch train/test metrics and plots
(baseline_training.py:201-260) — but the epoch body is one jit-compiled
device program per batch instead of a Python/torch CPU loop; the reference
needed ~17 min/epoch on an M1 CPU (BASELINE.md), a v5e chip does it in ~3 s.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..data.cifar import Dataset
from ..utils.metrics import device_fields
from .loop import EpochLoop, phase
from .optimizers import baseline_optimizer, server_sgd
from .steps import make_eval_step, make_train_step
from .train_state import create_train_state


@dataclass
class BaselineConfig:
    batch_size: int = 128          # baseline_training.py:203
    num_epochs: int = 3            # baseline_training.py:204
    learning_rate: float = 0.1     # baseline_training.py:205
    momentum: float = 0.9          # baseline_training.py:223
    weight_decay: float = 5e-4
    milestones: tuple = (10, 15)   # baseline_training.py:224
    gamma: float = 0.1
    augment: bool = True
    num_classes: int = 100
    dtype: str = "bfloat16"        # TPU-first default; 'float32' for parity
    plain_sgd: bool = False        # True = the distributed server optimizer
    model: str = "resnet18"        # models/registry.py name
    seed: int = 0
    # True = run each epoch as ONE compiled program over a device-resident
    # dataset (train/device_loop.py) — no per-batch host dispatch or
    # upload. False = per-batch host dispatch (the reference's DataLoader
    # shape, baseline_training.py:149-179).
    device_loop: bool = False


@dataclass
class TrainingMetrics:
    """Per-epoch records (baseline_training.py:97-147 TrainingMetrics)."""

    epochs: list = field(default_factory=list)
    train_losses: list = field(default_factory=list)
    train_accuracies: list = field(default_factory=list)
    test_accuracies: list = field(default_factory=list)
    epoch_times: list = field(default_factory=list)

    def add_epoch(self, epoch, loss, train_acc, test_acc, seconds):
        self.epochs.append(epoch)
        self.train_losses.append(float(loss))
        self.train_accuracies.append(float(train_acc))
        self.test_accuracies.append(float(test_acc))
        self.epoch_times.append(float(seconds))

    def plot_results(self, path: str) -> None:
        """4-panel summary plot (baseline_training.py:110-147)."""
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt

        fig, axes = plt.subplots(2, 2, figsize=(12, 8))
        axes[0, 0].plot(self.epochs, self.train_losses, "o-")
        axes[0, 0].set_title("Training loss")
        axes[0, 1].plot(self.epochs, self.train_accuracies, "o-",
                        label="train")
        axes[0, 1].plot(self.epochs, self.test_accuracies, "s-", label="test")
        axes[0, 1].set_title("Accuracy (%)")
        axes[0, 1].legend()
        axes[1, 0].bar(self.epochs, self.epoch_times)
        axes[1, 0].set_title("Epoch time (s)")
        axes[1, 1].axis("off")
        summary = (f"final test acc: "
                   f"{self.test_accuracies[-1]:.2f}%\n"
                   f"total time: {sum(self.epoch_times):.1f}s"
                   if self.epochs else "no epochs")
        axes[1, 1].text(0.1, 0.5, summary, fontsize=12)
        for ax in axes.flat:
            ax.set_xlabel("epoch")
        fig.tight_layout()
        fig.savefig(path, dpi=120)
        plt.close(fig)


class BaselineTrainer(EpochLoop):
    """The reference's baseline_training.py main loop as a class: the one
    epoch loop (train/loop.py) with the reference's line, its percentages
    and its ``TrainingMetrics`` record."""

    mode = "baseline"

    def __init__(self, dataset: Dataset, config: BaselineConfig | None = None,
                 model=None):
        self.config = cfg = config or BaselineConfig()
        self.dataset = dataset
        steps_per_epoch = max(
            1, len(dataset.x_train) // cfg.batch_size)
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        from ..models import get_model
        self.model = model or get_model(cfg.model,
                                        num_classes=cfg.num_classes,
                                        dtype=dtype,
                                        image_size=dataset.x_train.shape[1])
        tx = (server_sgd(cfg.learning_rate) if cfg.plain_sgd
              else baseline_optimizer(
                  cfg.learning_rate, cfg.momentum, cfg.weight_decay,
                  cfg.milestones, cfg.gamma, steps_per_epoch))
        h, w = dataset.x_train.shape[1:3]
        self.state = create_train_state(
            self.model, jax.random.PRNGKey(cfg.seed), tx,
            input_shape=(1, h, w, 3))
        self._step = jax.jit(make_train_step(augment=cfg.augment),
                             donate_argnums=0)
        self._eval_step = jax.jit(make_eval_step())
        self._device_loop = None
        if cfg.device_loop:
            from .device_loop import DeviceEpochLoop
            self._device_loop = DeviceEpochLoop(
                dataset, make_train_step(augment=cfg.augment),
                batch_size=cfg.batch_size)
        self.metrics = TrainingMetrics()
        self._init_loop()
        self._train_acc = 0.0      # the newest epoch's, in %

    # -- what the one epoch loop (train/loop.py) is handed --------------------
    def _train_batches(self, seed: int):
        return super()._train_batches(seed + 1)    # by the 1-based epoch

    def _fetch_step(self, m: dict) -> float:
        return float(m["accuracy"])

    def _epoch_synced(self, metrics: list, fetched: list) -> None:
        self._train_acc = 100.0 * float(np.mean(fetched))

    def _run_epoch(self, epoch: int, rng, tel) -> tuple[list, float]:
        if self._device_loop is None:
            return super()._run_epoch(epoch, rng, tel)
        # steps and evaluation as ONE program (train/device_loop.py)
        with phase("trainer.step", mode=self.mode, epoch=epoch,
                   step=self.global_steps), tel.goodput.span("compute"):
            self.state, em = self._device_loop.run_epoch(
                self.state, jax.random.fold_in(rng, epoch + 1))
        self.global_steps += self._device_loop.steps_per_epoch
        self._train_acc = 100.0 * em["train_accuracy"]
        return [em["train_loss"]], em["test_accuracy"]

    def _epoch_line(self, epoch, loss, acc, seconds) -> str:
        return (f"epoch {epoch + 1}/{self.config.num_epochs}: "
                f"loss {loss:.4f} train {self._train_acc:.2f}% "
                f"test {100.0 * acc:.2f}% ({seconds:.1f}s)")

    def _epoch_visible(self, epoch: int, acc: float) -> None:
        self.metrics.add_epoch(epoch + 1, self.epoch_losses[-1],
                               self._train_acc, 100.0 * acc,
                               self.epoch_times[-1])
        super()._epoch_visible(epoch, acc)

    def _final_metrics(self, total: float) -> dict:
        cfg, m = self.config, self.metrics
        return {
            "role": "baseline",
            "num_epochs": cfg.num_epochs,
            "batch_size": cfg.batch_size,
            "learning_rate": cfg.learning_rate,
            "total_training_time_seconds": round(sum(m.epoch_times), 2),
            "epoch_times_seconds": [round(t, 2) for t in m.epoch_times],
            "final_test_accuracy": (m.test_accuracies[-1] if m.epochs
                                    else None),
            "all_test_accuracies": m.test_accuracies,
            "final_train_loss": m.train_losses[-1] if m.epochs else None,
            **device_fields(),
        }

    def train(self, plot_path: str | None = None,
              emit_metrics: bool = False,
              checkpoint_dir: str | None = None,
              resume: bool = False) -> TrainingMetrics:
        super().train(emit_metrics, checkpoint_dir, resume)
        if plot_path:
            self.metrics.plot_results(plot_path)
        return self.metrics
