"""The SmallThinker cell's benchmark files: the operation count's numbers,
the configuration file against the published config, the driver
``sync_mesh_lm`` through the real harness on the CPU at a tiny size
(``tinybench``'s way: a temporary copy gains a tiny configuration, a traffic
mix and entries, as new files only), a planted fault that
``matches_reference`` must catch, and the new readers.
"""

import copy as copylib
import dataclasses
import json
import os

import numpy as np
import pytest

import tinybench
from harness import hlo_scopes, smallthinker_scopes, spec, xplane

REPO = spec.ROOT
CELL = "smallthinker-ep4-sync-16k-1chip"
JOYAI_CELL = "joyai-flash-ep16-sync-1chip"

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``SmallThinker-21BA3B-Instruct``), as published
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}

TINY = {
    "name": "smallthinker-tiny",
    "source": "tests only: models/smallthinker.py PRESETS",
    "model": "smallthinker", "ops_count": "smallthinker",
    "reference": "smallthinker_reference",
    "head_dim": 16, "hidden_size": 64, "max_position_embeddings": 64,
    "moe_ffn_hidden_size": 32, "moe_num_active_primary_experts": 2,
    "moe_num_primary_experts": 4, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1], "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": 8, "tie_word_embeddings": False,
    "vocab_size": 512,
    "published": {"moe_num_primary_experts": 8},
    "deployment": {"experts_key": "moe_num_primary_experts",
                   "first_expert_held": 2},
    "architecture": {
        "sequence_length": 64, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
        "moe_num_primary_experts_published": 8, "held_experts": 4,
        "moe_num_active_primary_experts": 2, "vocab_size": 512, "layers": 4,
        "sliding_window_size": 8, "sliding_window_layout": [0, 1, 1, 1]},
    "compute_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 0.003, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
    "data": {"kind": "bigram_documents", "median_len": 40, "sigma": 1.2,
             "branch": 8, "zipf_a": 1.1},
    "eval": {"held_out_sequences": 1},
    "reference_check": {"positions": 4, "head_block": 2},
    # float32 against the float32 reference: rounding alone
    "reference_limits": {"loss_rel": 1e-5, "logits_max": 1e-4,
                         "grad_l2_worst": 1e-3, "routing_moved": 0.0,
                         "update_l2": 1e-2, "rule_l2_worst": 1e-3,
                         "bias_moved": 0.0},
    "learned": {"min_loss_drop": 0.05},
    "assumed": {"values": {"init_std": 0.05}},
}
TINY_TRAFFIC = {"driver": "sync_mesh_lm", "per_chip_batch": 2,
                "seq_len": 64, "steps_per_epoch": 4,
                "exchange_dtype": "none", "trace_slice_s": 0.5}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell():
    return spec.load_cell(CELL)


@pytest.fixture(scope="module")
def st_copy(tmp_path_factory):
    """``tinybench``'s copy plus the tiny SmallThinker cell: two new files
    and entries; this cell's readers' lists gain it."""
    root = tinybench.make_copy(str(tmp_path_factory.mktemp("benchst")))
    for rel, text in (("configs/smallthinker-tiny.json", json.dumps(TINY)),
                      ("traffic/tiny-st.json", json.dumps(TINY_TRAFFIC))):
        path = os.path.join(root, "benchmarks", rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "smallthinker-tiny", "source": TINY["source"],
        "file": "benchmarks/configs/smallthinker-tiny.json", "reduced": [],
        "why": "tests only"})
    bench["workloads"].append({
        "name": "tiny-st", "config": "smallthinker-tiny",
        "traffic": "tiny-st", "chips": 1, "why": "tests only"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-st")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# -- the driver, at a tiny size on the CPU --------------------------------------

def test_the_tiny_cell_runs_through_the_lm_driver(st_copy):
    from harness import validate
    cell = spec.load_cell("tiny-st", st_copy)
    assert cell.traffic["driver"] == "sync_mesh_lm"
    p = tinybench.run_tiny(st_copy, "tiny-st", seconds=2.0, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert validate.check_last_line(p.stdout, owed=cell.end_to_end,
                                    trace=False) == []
    result = json.loads(p.stdout)
    checks = result["checks"]
    assert result["device"]["platform"] == "cpu"
    for clause in ("matches_reference", "learned", "no_token_dropped",
                   "tokens_reconcile", "losses_finite", "counts_reconcile",
                   "no_compile_in_window", "work_was_done"):
        assert checks[clause], (clause, checks)
    assert result["correct"] and result["failed"] == 0
    with open(os.path.join(st_copy, "chiprun_out", "benchmarks",
                           f"tiny-st.seed{2**31 + 11}.trace0",
                           "run.log")) as f:
        log = f.read()
    assert "matches_reference True" in log and "tokens/s/chip" in log
    # the edge carries the tile counters the band reader reads (the CPU's
    # programs hold dense_core, no kernel: nothing counted)
    assert "'flash_tiles': {'unmasked': 0" in log
    # half the experts are held: the other half's assignments are absent
    assert "to absent" in log and "dropped 0" in log


@pytest.fixture(scope="module")
def tiny_trainer(st_copy):
    """The tiny cell's trainer in this process, as the driver builds it."""
    driver = spec.load_module("drivers", "sync_mesh_lm",
                              os.path.join(st_copy, "benchmarks"))
    cell = spec.load_cell("tiny-st", st_copy)
    return (driver, cell) + driver.build_trainer(cell, 2**31 + 5, 1)


def test_the_driver_builds_the_model_from_the_registry(tiny_trainer):
    from distributed_parameter_server_for_ml_training_tpu.models import (
        smallthinker)
    driver, cell, trainer, _dataset, global_batch = tiny_trainer
    mc = trainer.task.model_config
    assert isinstance(mc, smallthinker.SmallThinkerConfig)
    # the router's published width, the experts held from the file's first
    assert mc.moe_num_primary_experts == 8 and mc.held_experts == (2, 4)
    assert mc.init_std == 0.05 and mc.expert_capacity_factor == 0.0
    assert global_batch == 2
    # and the other decoder's file goes the same way once it names its key
    joyai = copylib.deepcopy(spec.load_cell(JOYAI_CELL).config)
    joyai["deployment"]["experts_key"] = "n_routed_experts"
    from distributed_parameter_server_for_ml_training_tpu.models.joyai \
        import PRESETS
    assert driver.model_config(joyai) == PRESETS["ep16"]


def test_the_readings_pool_is_held_to_what_the_file_says():
    """``readings`` asks ``concurrent.futures`` for a pool of four; the
    driver holds it to ``reference_check.tensors_at_a_time`` while the
    comparison lasts and hands the class back."""
    import concurrent.futures as cf
    driver = spec.load_module("drivers", "sync_mesh_lm", spec.BENCH_DIR)
    real = cf.ThreadPoolExecutor
    with driver.tensors_at_a_time(1):
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=4) as pool:
            assert pool._max_workers == 1
            assert list(pool.map(abs, [-1, 2])) == [1, 2]
    with driver.tensors_at_a_time(8):
        with cf.ThreadPoolExecutor(max_workers=4) as pool:
            assert pool._max_workers == 4
    assert cf.ThreadPoolExecutor is real
    # 389 MB tensors, a dozen temporaries each, beside 21 GB of states
    assert _cell().config["reference_check"]["tensors_at_a_time"] == 1


def test_the_trainers_own_step_matches_and_both_controls_do_not(
        tiny_trainer, capsys):
    driver, cell, trainer, dataset, global_batch = tiny_trainer
    before = np.asarray(trainer.state.params["embed"])
    base = driver._BASE
    ok, found = base.compare_with_reference(
        cell, 2**31 + 5, trainer, dataset, global_batch, control="bfloat16")
    assert ok, found
    assert 0.0 < found["update_l2"] < 1e-2 and found["bias_moved"] == 0.0
    control = driver.full_attention_control(cell, 2**31 + 5, trainer,
                                            dataset, global_batch)
    assert not base.within(control, cell.config["reference_limits"])
    assert control["grad_l2_worst"] > 100 * found["grad_l2_worst"]
    # the run starts from the seed's state, not from a step's
    assert int(trainer.state.step) == 0
    assert np.array_equal(np.asarray(trainer.state.params["embed"]), before)
    out = capsys.readouterr().out
    assert "control: the reference in bfloat16" in out
    assert "control: the reference without its window" in out
    assert out.count("rejected True") == 2


def test_a_step_whose_window_layers_attend_to_the_whole_prefix_does_not_match(
        tiny_trainer):
    """The planted fault: the trainer's own step with a model that forgot
    the band (every layer global). Loss, logits and gradients all move; the
    update's rule, given the step's own gradient, is still sound."""
    from distributed_parameter_server_for_ml_training_tpu.models import (
        get_model)
    driver, cell, trainer, dataset, global_batch = tiny_trainer
    mc = trainer.task.model_config
    forgot = get_model("smallthinker", dtype=trainer.model.dtype,
                       config=dataclasses.replace(
                           mc, sliding_window_layout=(0, 0, 0, 0)))

    def no_band(state, tokens, rng):
        return trainer._step(state.replace(apply_fn=forgot.apply), tokens,
                             rng)

    ok, found = driver._BASE.compare_with_reference(
        cell, 2**31 + 5, trainer, dataset, global_batch, step=no_band)
    assert not ok
    limits = cell.config["reference_limits"]
    assert found["loss_rel"] > 10 * limits["loss_rel"]
    assert found["grad_l2_worst"] > 100 * limits["grad_l2_worst"]
    assert found["update_l2"] > 10 * limits["update_l2"]
    assert found["rule_l2_worst"] < limits["rule_l2_worst"]


# -- the operation count --------------------------------------------------------

def test_the_cells_operation_and_parameter_counts_are_the_issues():
    cell = _cell()
    ops = spec.load_module("ops_count", "smallthinker", spec.BENCH_DIR)
    arch = cell.config["architecture"]
    assert cell.parameter_count() == 656_529_920
    assert ops.pairs_per_head(arch, windowed=False) == 134_225_920
    assert ops.pairs_per_head(arch, windowed=True) == 58_722_304
    # matmuls 3.56 T and scores 2.22 T multiply-accumulates a sequence
    scores = sum(ops.score_macs(arch, w) for w in (False, True, True, True))
    assert scores == (134_225_920 + 3 * 58_722_304) * 28 * 256
    matmuls = ops.forward_macs(arch) - scores
    assert matmuls == 16384 * (4 * (20_971_520 + 163_840
                                    + 1.5 * 3 * 2560 * 768)
                               + 2560 * 37_984)
    assert abs(scores / 1e12 - 2.225) < 1e-3
    assert abs(matmuls / 1e12 - 3.558) < 1e-3
    assert cell.train_flops_per_image() == 6.0 * (matmuls + scores)
    assert abs(cell.train_flops_per_image() / 1e12 - 34.70) < 0.01


@pytest.mark.parametrize("key, value, grows", [
    ("layers", None, True), ("vocab_size", 2 * 37_984, True),
    ("held_experts", 32, True), ("sequence_length", 32_768, True),
    ("sliding_window_size", 2048, False),
    ("sliding_window_size", 16_384, True)])
def test_the_count_follows_each_shape(key, value, grows):
    ops = spec.load_module("ops_count", "smallthinker", spec.BENCH_DIR)
    arch = dict(_cell().config["architecture"])
    base = ops.forward_macs(arch)
    if key == "layers":        # a second period
        arch.update(layers=8, sliding_window_layout=[0, 1, 1, 1] * 2)
    else:
        arch[key] = value
    assert (ops.forward_macs(arch) > base) is grows
    assert ops.forward_macs(arch) != base
    # a window as long as the sequence is plain causal attention
    whole = dict(arch, sliding_window_size=arch["sequence_length"])
    assert ops.pairs_per_head(whole, True) == ops.pairs_per_head(whole, False)


def test_the_kernels_costs_are_the_mean_over_one_global_and_three_window_calls():
    ops = spec.load_module("ops_count", "smallthinker", spec.BENCH_DIR)
    arch = _cell().config["architecture"]
    mean_pairs = 28 * (134_225_920 + 3 * 58_722_304) / 4
    q_rows, kv_rows = 28 * 16384, 4 * 16384
    want = {   # matmuls of 128 a pair; bytes: q-head rows, kv-head rows
        "fwd": (2, q_rows * (2 * 2 * 128 + 4) + kv_rows * 2 * 2 * 128),
        "bwd_dq": (3, q_rows * (2 * 3 * 128 + 8) + kv_rows * 2 * 2 * 128),
        "bwd_dkv": (4, q_rows * (2 * 2 * 128 + 8) + kv_rows * 2 * 4 * 128)}
    for kernel, (matmuls, nbytes) in want.items():
        operations, got = getattr(ops, f"flash_attention_{kernel}_cost")(
            arch, 1)
        assert operations == 2.0 * mean_pairs * matmuls * 128
        assert got == nbytes
        assert operations / 197e12 > got / 819e9      # compute-bound
        twice = getattr(ops, f"flash_attention_{kernel}_cost")(arch, 2)
        assert twice == (2 * operations, 2 * got)
    # all global: T(T+1)/2 pairs a head, K and V still once a KV head
    plain = dict(arch, sliding_window_layout=[0, 0, 0, 0])
    assert ops.flash_attention_fwd_cost(plain, 1)[0] \
        == 2.0 * 28 * 134_225_920 * 256


# -- the configuration file -----------------------------------------------------

def test_the_configuration_holds_every_published_number_unchanged():
    config = _cell().config
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "moe_num_primary_experts",
                       "vocab_size", "data", "eval"}
    assert reduced <= set(config)
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), \
                key
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 37_984)
    # the floors: a whole period and four layers, 16 >= 8 experts, a
    # quarter >= an eighth of the vocabulary
    assert config["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"],
            deployment["expert_parallel"],
            deployment["vocabulary_parallel"],
            deployment["first_expert_held"]) == (4, 4, 4, 0)
    assert set(config["assumed"]["values"]) == {"init_std",
                                                "expert_capacity_factor"}
    assert set(config["optimizer"]) >= {"learning_rate", "b1", "b2", "eps",
                                        "weight_decay"}
    assert config["data"] == dict(kind="bigram_documents", median_len=1600,
                                  sigma=1.5, branch=8, zipf_a=1.1)
    for marked in ("router", "attention", "loss"):
        assert "†" in config["assumed"][marked]
    assert "secondary" in config["assumed"]["experts"]


def test_the_architecture_group_and_the_programs_preset_say_the_same():
    from distributed_parameter_server_for_ml_training_tpu.models \
        .smallthinker import PRESETS
    driver = spec.load_module("drivers", "sync_mesh_lm", spec.BENCH_DIR)
    cell = _cell()
    config, arch = cell.config, cell.config["architecture"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_ffn_hidden_size", "sliding_window_size",
                "moe_num_active_primary_experts", "vocab_size"):
        assert arch[key] == config[key], key
    assert arch["held_experts"] == config["moe_num_primary_experts"]
    assert arch["moe_num_primary_experts_published"] \
        == config["published"]["moe_num_primary_experts"]
    assert arch["layers"] == config["num_hidden_layers"]
    assert arch["sliding_window_layout"] \
        == config["sliding_window_layout"][:arch["layers"]]
    assert arch["sequence_length"] == cell.traffic["seq_len"] \
        == config["max_position_embeddings"]
    assert driver.model_config(config) == PRESETS["ep4"]
    traffic = cell.traffic
    assert (traffic["driver"], traffic["per_chip_batch"],
            traffic["steps_per_epoch"]) == ("sync_mesh_lm", 1, 8)
    assert config["eval"]["held_out_sequences"] == 1


def test_the_accepted_entries_list_the_cells_they_listed():
    """What ``test_bench_lm_cell.py``'s test of the five accepted entries
    holds, without its last line, which counted four cells (tests/
    conftest.py: ``OVERTAKEN``)."""
    bench = _bench()
    image = ["resnet18-sync-1chip", "vit-b16-sync-1chip",
             "vit-b16-sync-4chip"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("trainer.dispatch_ms", "trainer.epoch_end_host_ms",
                 "trainer.input_ms", "device.idle_named_share",
                 "device.step_period_ms_max"):
        assert by_name[name]["workloads"] == image
        assert by_name["lm." + name]["workloads"] == [JOYAI_CELL]
    for name in ("mla.device_ms", "flash_attention_fwd_roofline",
                 "moe.route_ms", "data.packing_waste"):
        assert by_name[name]["workloads"] == [JOYAI_CELL]
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [w["chips"] for w in bench["workloads"]] == [1, 1, 4, 1, 1]
    owed = _cell().per_layer
    new = {m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]}
    assert new == {
        "window_attention.device_ms", "full_attention.device_ms",
        "st.moe.route_ms", "st.moe.experts_ms", "st.optimizer.device_ms",
        "st.flash_attention_fwd_roofline",
        "st.flash_attention_bwd_dq_roofline",
        "st.flash_attention_bwd_dkv_roofline", "attention.band_tile_share",
        "st.moe.load_max_over_mean"}
    # and what every cell owes because its entry lists no cells
    assert set(owed) == new | {
        "entry.compile_s", "entry.cache_misses",
        "trainer.compiles_in_window", "step.device_ms", "step_roofline",
        "device.idle_share"}
    readers = spec.declared_layer_metrics()
    for name in new:
        assert readers[name].DRIVERS == ("sync_mesh_lm",)


# -- the readers ----------------------------------------------------------------

_PATH = "jit(worker_step)/forward_backward/jvp(SmallThinkerLM)/" \
        "SmallThinkerLM.hidden/"
HLO = f'''
ENTRY %main {{
  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="{_PATH}layer_1/attn_window/attn/q/dot_general" stack_frame_id=3}}
  %fusion.2 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="jit(worker_step)/forward_backward/transpose(jvp(SmallThinkerLM))/SmallThinkerLM.hidden/layer_0/attn_full/attn/o/dot_general"}}
  %ragged-dot-none.4 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %sort.7 = s32[8]{{0}} sort(%p), dimensions={{0}}, metadata={{op_name="sort"}}
  %multiply_add_fusion.3 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="jit(worker_step)/update/add"}}
  %copy.9 = f32[8]{{0}} copy(%p)
  %flash_attention_fwd.2 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{_PATH}layer_2/attn_window/attn/flash_attention_fwd/pallas_call"}}
}}
'''


class _Run:
    """What a reader is handed, with a synthetic traced slice: two step
    runs of 10 ms on device 0, each holding the instructions of ``HLO``."""

    def __init__(self, cell, with_trace=True):
        self.cell = cell
        self.images_per_device_step = 1
        self.peak = cell.peak("TPU v5 lite")
        self.edges = ({"t": 0.0}, {
            "t": 1.0, "moe_load_max_over_mean": 1.3,
            "flash_tiles": {"unmasked": 1084.0, "masked": 200.0,
                            "skipped": 2000.0, "below_band": 828.0}})
        self.trace = None
        if with_trace:
            ms = 1e6
            ops, steps = [], []
            for k in range(2):
                lo = k * 20 * ms
                steps.append((f"jit_worker_step({k})", lo, lo + 10 * ms))
                at = lo
                for name, dur in (("fusion.1", 2.0), ("fusion.2", 1.0),
                                  ("ragged-dot-none.4", 1.5),
                                  ("sort.7", 0.5),
                                  ("multiply_add_fusion.3", 3.0),
                                  ("copy.9", 0.25),
                                  ("flash_attention_fwd.2", 1.0)):
                    text = f"%{name} = f32[8]{{0}} fusion(%p)"
                    ops.append((text, at, at + dur * ms))
                    at += dur * ms
            device = xplane.DeviceReduction(
                0, (0.0, 30 * ms), 18.5 * ms, steps, steps, ops,
                xplane.union((s, e) for (_n, s, e) in ops))
            self.trace = xplane.TraceReduction("jit_worker_step", [device])


@pytest.fixture()
def kept():
    hlo_scopes.KEPT["jit_worker_step"] = HLO
    yield
    hlo_scopes.KEPT.clear()


SCOPE_READERS = {"window_attention.device_ms": 3.0,
                 "full_attention.device_ms": 1.0, "st.moe.experts_ms": 1.5,
                 "st.moe.route_ms": 0.5, "st.optimizer.device_ms": 3.0}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_a_scope_reader_charges_each_instruction_once(name, kept, capsys):
    """The window layers' kernel call is the window layers', instructions
    whose path XLA lost go by their name, and what has no name at all is
    ``other``; a program without the scope, or a run that kept no text,
    reads ``None``."""
    read = spec.load_module("layer_metrics", name, spec.BENCH_DIR).read
    assert read(_Run(_cell())) == pytest.approx(SCOPE_READERS[name])
    assert "other 0.250" in capsys.readouterr().out
    assert read(_Run(_cell(), with_trace=False)) is None
    if not name.startswith("st.moe"):    # a program without these scopes
        hlo_scopes.KEPT["jit_worker_step"] = HLO.replace(
            "attn_window", "mla").replace("attn_full", "mla").replace(
            "update", "sgd")
        assert read(_Run(_cell())) is None
    hlo_scopes.KEPT.clear()      # a program whose driver kept no text
    assert read(_Run(_cell())) is None
    assert smallthinker_scopes.SCOPES[-1] == "forward_backward"


def test_a_kernels_roofline_counts_its_calls_at_the_mean_cost(kept):
    read = spec.load_module("layer_metrics",
                            "st.flash_attention_fwd_roofline",
                            spec.BENCH_DIR).read
    ops = spec.load_module("ops_count", "smallthinker", spec.BENCH_DIR)
    operations, _bytes = ops.flash_attention_fwd_cost(
        _cell().config["architecture"], 1)
    # two calls of 1 ms each in the slice
    assert read(_Run(_cell())) == pytest.approx(
        100.0 * 2 * (operations / 197e12) / 2e-3)
    assert read(_Run(_cell(), with_trace=False)) is None
    none = spec.load_module("layer_metrics",
                            "st.flash_attention_bwd_dkv_roofline",
                            spec.BENCH_DIR).read
    assert none(_Run(_cell())) is None       # no such event in the slice


def test_the_counter_readers_read_the_last_edge():
    run = _Run(_cell(), with_trace=False)
    band = spec.load_module("layer_metrics", "attention.band_tile_share",
                            spec.BENCH_DIR).read
    load = spec.load_module("layer_metrics", "st.moe.load_max_over_mean",
                            spec.BENCH_DIR).read
    assert band(run) == pytest.approx(1284 / 2112)     # (528 + 3 x 252) / ..
    assert load(run) == 1.3
    run.edges = ({"t": 0.0}, {"t": 1.0})      # an older program's edge
    assert band(run) is None and load(run) is None
    run.edges = ({"t": 0.0}, {"t": 1.0, "flash_tiles": {
        "unmasked": 0.0, "masked": 0.0, "skipped": 0.0, "below_band": 0.0}})
    assert band(run) is None                  # a program with no kernel


def test_the_band_readers_docstring_is_tile_plans_count():
    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        flash_attention as fa)
    assert fa.pick_block(16384) == 512
    full = fa.tile_plan(16384, 16384, 16384, 512, 512, True)
    band = fa.tile_plan(16384, 16384, 16384, 512, 512, True, 4096)
    assert full == {"unmasked": 496, "masked": 32, "skipped": 496}
    assert band == {"unmasked": 196, "masked": 56, "skipped": 496,
                    "below_band": 276}
    computed = 528 + 3 * 252
    assert computed / (4 * 528) == pytest.approx(0.608, abs=5e-4)


#: the comparison's readings on the chip (my chip runs, PR 34, through
#: benchmarks/run.py): the trainer's step against the float32 reference,
#: the first seven seeds; then the two controls against the same, one seed
#: each
CHIP_SOUND = [
    {"loss_rel": 1.232e-04, "logits_max": 0.006941, "grad_l2_worst": 0.03084,
     "grad_l2_median": 0.01685, "update_l2": 0.1523,
     "rule_l2_worst": 8.593e-06, "routing_moved": 0.006498},
    {"loss_rel": 2.118e-05, "logits_max": 0.01537, "grad_l2_worst": 0.04796,
     "grad_l2_median": 0.01746, "update_l2": 0.1639,
     "rule_l2_worst": 9.242e-06, "routing_moved": 0.002970},
    {"loss_rel": 1.173e-05, "logits_max": 0.007214, "grad_l2_worst": 0.02729,
     "grad_l2_median": 0.01152, "update_l2": 0.1419,
     "rule_l2_worst": 9.550e-06, "routing_moved": 0.004107},
    {"loss_rel": 2.469e-05, "logits_max": 0.01635, "grad_l2_worst": 0.05580,
     "grad_l2_median": 0.01075, "update_l2": 0.1431,
     "rule_l2_worst": 8.911e-06, "routing_moved": 0.002762},
    {"loss_rel": 1.025e-04, "logits_max": 0.04400, "grad_l2_worst": 0.02690,
     "grad_l2_median": 0.009761, "update_l2": 0.1335,
     "rule_l2_worst": 9.459e-06, "routing_moved": 0.002703},
    {"loss_rel": 5.089e-05, "logits_max": 0.006606, "grad_l2_worst": 0.07168,
     "grad_l2_median": 0.01115, "update_l2": 0.1409,
     "rule_l2_worst": 1.097e-05, "routing_moved": 0.003525},
    {"loss_rel": 1.218e-04, "logits_max": 0.03099, "grad_l2_worst": 0.03466,
     "grad_l2_median": 0.01164, "update_l2": 0.1372,
     "rule_l2_worst": 8.320e-06, "routing_moved": 0.003382}]
#: the eighth sound seed (3400022): a router's gradient, 0.328
CHIP_SOUND.append(
    {"loss_rel": 2.790e-05, "logits_max": 0.03335, "grad_l2_worst": 0.3282,
     "grad_l2_median": 0.01031, "update_l2": 0.1383,
     "rule_l2_worst": 9.420e-06, "routing_moved": 0.003031})
CHIP_BF16 = {"loss_rel": 0.002326, "logits_max": 0.008606,
             "grad_l2_worst": 0.06477, "grad_l2_median": 0.01782,
             "update_l2": 0.1482, "rule_l2_worst": 3.295e-06,
             "routing_moved": 0.005554}
CHIP_FULL_ATTENTION = {"loss_rel": 0.0002491, "logits_max": 0.1165,
                       "grad_l2_worst": 1.107, "grad_l2_median": 0.09702,
                       "update_l2": 0.4214, "rule_l2_worst": 2.169e-06,
                       "routing_moved": 0.01735}


def test_the_cells_limits_pass_the_chips_sound_readings_and_reject_both_controls():
    """Each limit has room on both sides of what the chip read. The bf16
    reference fails by ``loss_rel`` alone (precision hardly moves the
    rest); the reference without its window by five limits, and would pass
    ``loss_rel``."""
    driver = spec.load_module("drivers", "sync_mesh_lm", spec.BENCH_DIR)
    within = driver._BASE.within
    limits = _cell().config["reference_limits"]
    assert set(limits) == set(CHIP_SOUND[0])       # no ``bias_moved``
    for found in CHIP_SOUND:
        assert within(found, limits)
        assert within({k: 1.6 * v for k, v in found.items()}, limits)
    assert not within(CHIP_BF16, limits)
    assert CHIP_BF16["loss_rel"] > 5 * limits["loss_rel"]
    assert within(dict(CHIP_BF16, loss_rel=0.0), limits)
    assert not within(CHIP_FULL_ATTENTION, limits)
    for key in ("logits_max", "grad_l2_median", "grad_l2_worst",
                "update_l2", "routing_moved"):
        assert CHIP_FULL_ATTENTION[key] > 1.35 * limits[key], key
    # what an unchanged state, a zero gradient, a decayed gain read
    for key, reads in (("update_l2", 1.0), ("grad_l2_median", 1.0),
                       ("grad_l2_worst", 1.0), ("rule_l2_worst", 0.1)):
        assert limits[key] < 0.7 * reads
    assert _cell().config["learned"] == {"min_loss_drop": 1.0}
    assert _cell().config["assumed"]["values"]["expert_capacity_factor"] \
        == 4.0
