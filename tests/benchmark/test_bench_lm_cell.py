"""The decoder-LM cell's benchmark files: the new driver, token data and
readers through the real harness on the CPU at a tiny size (``tinybench``'s
way: a temporary copy gains a tiny configuration, a traffic mix and entries,
as new files only), the operation count's cases, the configuration file
against the published config, and the two reference copies.
"""

import copy as copylib
import json
import os

import numpy as np
import pytest

import tinybench
from harness import hlo_scopes, spec, tokens, validate, xplane

REPO = spec.ROOT
CELL = "joyai-flash-ep16-sync-1chip"

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``JoyAI-LLM-Flash``), as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}

TINY_LM = {
    "name": "joyai-tiny", "source": "tests only: models/joyai.py PRESETS",
    "model": "joyai_llm_flash", "ops_count": "joyai_llm_flash",
    "reference": "joyai_reference",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 32000000, "scoring_func": "sigmoid",
    "published": {"n_routed_experts": 16},
    "deployment": {"first_expert_held": 4},
    "architecture": {
        "sequence_length": 64, "hidden_size": 64, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 160,
        "moe_intermediate_size": 32, "n_routed_experts_published": 16,
        "held_experts": 4, "num_experts_per_tok": 4, "n_shared_experts": 1,
        "vocab_size": 512, "dense_layers": 1, "expert_layers": 2,
        "mtp_modules": 1},
    "compute_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 0.003, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
    "data": {"kind": "bigram_documents", "median_len": 40, "sigma": 1.2,
             "branch": 8, "zipf_a": 1.1},
    "eval": {"held_out_sequences": 2},
    "reference_check": {"positions": 4, "head_block": 2},
    # float32 against the float32 reference: rounding alone
    "reference_limits": {"loss_rel": 1e-5, "logits_max": 1e-4,
                         "grad_l2_worst": 1e-3, "routing_moved": 0.0,
                         "update_l2": 1e-2, "rule_l2_worst": 1e-3,
                         "bias_moved": 0.0},
    "learned": {"min_loss_drop": 0.05},
    "assumed": {"values": {"mtp_lambda": 0.3, "bias_update_gamma": 0.001,
                           "init_std": 0.05}},
}
TINY_TRAFFIC = {"driver": "sync_mesh_tokens", "per_chip_batch": 2,
                "seq_len": 64, "steps_per_epoch": 4,
                "exchange_dtype": "none", "trace_slice_s": 0.5}


@pytest.fixture(scope="module")
def lm_copy(tmp_path_factory):
    """``tinybench``'s copy plus the tiny LM cell: two new files and
    entries; the LM readers' lists gain the cell."""
    root = tinybench.make_copy(str(tmp_path_factory.mktemp("benchlm")))
    for rel, text in (("configs/joyai-tiny.json", json.dumps(TINY_LM)),
                      ("traffic/tiny-lm.json", json.dumps(TINY_TRAFFIC))):
        path = os.path.join(root, "benchmarks", rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "joyai-tiny", "source": TINY_LM["source"],
        "file": "benchmarks/configs/joyai-tiny.json", "reduced": [],
        "why": "tests only"})
    bench["workloads"].append({
        "name": "tiny-lm", "config": "joyai-tiny", "traffic": "tiny-lm",
        "chips": 1, "why": "tests only"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-lm")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_tiny_lm_cell_runs_through_the_token_driver(lm_copy):
    cell = spec.load_cell("tiny-lm", lm_copy)
    assert cell.traffic["driver"] == "sync_mesh_tokens"
    p = tinybench.run_tiny(lm_copy, "tiny-lm", seconds=2.0, timeout=600)
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
    with open(os.path.join(lm_copy, "chiprun_out", "benchmarks",
                           f"tiny-lm.seed{2**31 + 11}.trace0",
                           "run.log")) as f:
        log = f.read()
    assert "matches_reference True" in log and "tokens/s/chip" in log
    assert "packing waste" in log
    # the text is kept in a traced run alone
    assert "kept the step's HLO text" not in log


def test_a_limit_the_run_cannot_meet_makes_it_incorrect(lm_copy):
    """The comparison binds: with a limit under float32 rounding the same
    run is not correct."""
    strict = copylib.deepcopy(TINY_LM)
    strict["reference_limits"] = {"grad_l2_worst": 1e-12}
    path = os.path.join(lm_copy, "benchmarks", "configs", "joyai-tiny.json")
    with open(path, "w") as f:
        json.dump(strict, f)
    try:
        p = tinybench.run_tiny(lm_copy, "tiny-lm", seconds=1.0, timeout=600)
    finally:
        with open(path, "w") as f:
            json.dump(TINY_LM, f)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout)
    assert not result["checks"]["matches_reference"]
    assert not result["correct"]


# -- the comparison holds the step the window times -----------------------------

@pytest.fixture(scope="module")
def tiny_trainer(lm_copy):
    """The tiny cell's trainer in this process, as the driver builds it."""
    driver = spec.load_module("drivers", "sync_mesh_tokens",
                              os.path.join(lm_copy, "benchmarks"))
    cell = spec.load_cell("tiny-lm", lm_copy)
    return (driver, cell) + driver.build_trainer(cell, 2**31 + 5, 1)


def test_the_trainers_own_step_matches_and_a_lower_precision_does_not(
        tiny_trainer, capsys):
    driver, cell, trainer, dataset, global_batch = tiny_trainer
    before = np.asarray(trainer.state.params["embed"])
    ok, found = driver.compare_with_reference(
        cell, 2**31 + 5, trainer, dataset, global_batch, control="bfloat16")
    assert ok, found
    assert 0.0 < found["update_l2"] < 1e-2 and found["bias_moved"] == 0.0
    # the run starts from the seed's state, not from the step's
    assert int(trainer.state.step) == 0
    assert np.array_equal(np.asarray(trainer.state.params["embed"]), before)
    out = capsys.readouterr().out
    assert "control: the reference in bfloat16" in out
    assert "rejected True" in out


def test_a_step_that_leaves_the_state_unchanged_does_not_match(tiny_trainer):
    import jax
    import jax.numpy as jnp
    driver, cell, trainer, dataset, global_batch = tiny_trainer

    def unchanged(state, tokens, rng):
        _new, metrics = trainer._step(
            jax.tree_util.tree_map(jnp.copy, state), tokens, rng)
        return state, metrics

    ok, found = driver.compare_with_reference(
        cell, 2**31 + 5, trainer, dataset, global_batch, step=unchanged)
    assert not ok
    # no moment, no change: each reads what nothing reads
    assert found["grad_l2_median"] == pytest.approx(1.0)
    assert found["update_l2"] == pytest.approx(1.0)
    assert found["bias_moved"] > 0.5
    assert found["loss_rel"] < 1e-5        # the loss alone would pass


def test_a_step_that_trains_on_half_its_shard_does_not_match(tiny_trainer):
    import jax.numpy as jnp
    driver, cell, trainer, dataset, global_batch = tiny_trainer

    def half(state, tokens, rng):
        n = tokens.shape[0] // 2
        return trainer._step(
            state, jnp.concatenate([tokens[:n], tokens[:n]]), rng)

    ok, found = driver.compare_with_reference(
        cell, 2**31 + 5, trainer, dataset, global_batch, step=half)
    assert not ok
    limits = cell.config["reference_limits"]
    assert found["grad_l2_worst"] > 100 * limits["grad_l2_worst"]
    assert found["update_l2"] > 10 * limits["update_l2"]
    # the rule is sound: given the step's own gradient the update follows
    assert found["rule_l2_worst"] < limits["rule_l2_worst"]


def test_an_update_with_other_values_fails_the_rule_alone(tiny_trainer):
    """A second-moment decay, or a decay on the norms' gains, that is not
    the configuration's leaves loss and gradient as they are and shows in
    ``rule_l2_worst``."""
    import copy
    driver, cell, trainer, dataset, global_batch = tiny_trainer
    other = copy.copy(cell)
    other.config = copylib.deepcopy(cell.config)
    other.config["optimizer"]["b2"] = 0.999
    ok, found = driver.compare_with_reference(
        other, 2**31 + 5, trainer, dataset, global_batch)
    assert not ok
    assert found["grad_l2_worst"] < 1e-3 and found["loss_rel"] < 1e-5
    assert found["rule_l2_worst"] > 0.5        # nu is 50 times off


def test_a_traced_runs_step_is_one_executable_with_its_text_kept(capsys):
    import jax
    import jax.numpy as jnp
    calls = []

    def f(x):
        calls.append(1)            # traced once
        return x * 2.0

    step = hlo_scopes.KeptStep("jit_f", jax.jit(f))
    try:
        assert float(step(jnp.float32(3.0))) == 6.0
        assert float(step(jnp.float32(4.0))) == 8.0
        assert len(calls) == 1 and "multiply" in hlo_scopes.KEPT["jit_f"]
        assert "kept the step's HLO text" in capsys.readouterr().out
    finally:
        hlo_scopes.KEPT.clear()


# -- token data ---------------------------------------------------------------

def test_token_data_is_the_programs_construction_and_seeded():
    from distributed_parameter_server_for_ml_training_tpu.data import (
        tokens as program)
    config = {"vocab_size": 512, "eval": {"held_out_sequences": 2},
              "data": TINY_LM["data"]}
    a = tokens.make_token_dataset(config, {"seq_len": 64}, 8, 2**31 + 5)
    b = tokens.make_token_dataset(config, {"seq_len": 64}, 8, 2**31 + 5)
    c = tokens.make_token_dataset(config, {"seq_len": 64}, 8, 2**31 + 6)
    want = program.synthetic_documents(
        vocab_size=512, seq_len=64, n_train=8, n_test=2, seed=2**31 + 5,
        median_len=40, sigma=1.2)
    assert np.array_equal(a.train, b.train)
    assert not np.array_equal(a.train, c.train)
    assert np.array_equal(a.train, want.train)
    assert np.array_equal(a.test, want.test)
    assert a.packing_waste == want.packing_waste > 0
    assert a.train.shape == (8, 66) and a.train.max() < 512
    with pytest.raises(ValueError):
        tokens.make_token_dataset(dict(config, data={"kind": "corpus"}),
                                  {"seq_len": 64}, 8, 1)


# -- the operation count ------------------------------------------------------

def _cell():
    return spec.load_cell(CELL)


def test_the_cells_operation_and_parameter_counts_are_the_issues():
    cell = _cell()
    assert cell.parameter_count() == 680_441_088
    flops = cell.train_flops_per_image()
    assert abs(flops - 10.827293786112e12) < 1e6
    assert abs(flops / 4096 - 2.643382272e9) < 1e3     # a token


@pytest.mark.parametrize("key, factor, grows", [
    ("expert_layers", 2, True), ("dense_layers", 2, True),
    ("vocab_size", 2, True), ("held_experts", 2, True),
    ("mtp_modules", 0, False), ("sequence_length", 2, True)])
def test_the_count_follows_each_shape(key, factor, grows):
    ops = spec.load_module("ops_count", "joyai_llm_flash", spec.BENCH_DIR)
    arch = dict(_cell().config["architecture"])
    base = ops.forward_macs(arch)
    arch[key] = arch[key] * factor
    assert (ops.forward_macs(arch) > base) is grows
    assert ops.forward_macs(arch) != base


def test_the_counts_pieces_by_hand():
    ops = spec.load_module("ops_count", "joyai_llm_flash", spec.BENCH_DIR)
    arch = _cell().config["architecture"]
    # scores: T(T+1)/2 pairs x 32 heads x (192 + 128)
    assert ops.causal_score_macs(arch) == 4096 * 4097 // 2 * 32 * 320
    # half an expert a token: 8 x 16 / 256
    one = dict(arch, dense_layers=0, expert_layers=1, mtp_modules=0,
               vocab_size=0)
    none = dict(one, held_experts=0)
    assert ops.forward_macs(one) - ops.forward_macs(none) \
        == 4096 * 0.5 * 3 * 2048 * 768
    # the kernels: forward 2 matmuls, dq 3, dkv 4 over the causal pairs
    pairs = 4 * 32 * 4096 * 4097 // 2
    assert ops.flash_attention_fwd_cost(arch, 4)[0] == 2.0 * pairs * 320
    assert ops.flash_attention_bwd_dq_cost(arch, 4)[0] == 2.0 * pairs * 512
    assert ops.flash_attention_bwd_dkv_cost(arch, 4)[0] == 2.0 * pairs * 640
    for cost in (ops.flash_attention_fwd_cost,
                 ops.flash_attention_bwd_dq_cost,
                 ops.flash_attention_bwd_dkv_cost):
        operations, nbytes = cost(arch, 4)
        assert operations / 197e12 > nbytes / 819e9   # compute-bound


# -- the configuration file ---------------------------------------------------

def test_the_configuration_holds_every_published_number_unchanged():
    config = _cell().config
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size", "data", "eval"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), \
                key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16160)
    assert config["deployment"]["chips_sharing_a_layer"] == 16
    assert set(config["assumed"]["values"]) == {
        "mtp_lambda", "bias_update_gamma", "init_std",
        "expert_capacity_factor"}
    assert set(config["optimizer"]) >= {"learning_rate", "b1", "b2", "eps",
                                        "weight_decay"}


def test_the_architecture_group_and_the_programs_preset_say_the_same():
    from distributed_parameter_server_for_ml_training_tpu.models.joyai \
        import PRESETS, JoyAIConfig
    config = _cell().config
    arch = config["architecture"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts", "vocab_size"):
        assert arch[key] == config[key], key
    assert arch["held_experts"] == config["n_routed_experts"]
    assert arch["n_routed_experts_published"] \
        == config["published"]["n_routed_experts"]
    assert arch["dense_layers"] == config["first_k_dense_replace"]
    assert arch["dense_layers"] + arch["expert_layers"] \
        == config["num_hidden_layers"]
    assert arch["mtp_modules"] == config["num_nextn_predict_layers"]
    assert arch["sequence_length"] == _cell().traffic["seq_len"]
    built = JoyAIConfig.from_hf(
        config, n_routed_experts=config["published"]["n_routed_experts"],
        held_experts=(config["deployment"]["first_expert_held"],
                      config["n_routed_experts"]),
        **config["assumed"]["values"])
    assert built == PRESETS["ep16"]
    with pytest.raises(ValueError):
        JoyAIConfig.from_hf(dict(config, n_group=8))


def test_the_two_reference_copies_are_byte_identical():
    with open(os.path.join(REPO, "tests", "reference",
                           "joyai_reference.py"), "rb") as a, \
            open(os.path.join(spec.BENCH_DIR, "reference",
                              _cell().config["reference"] + ".py"),
                 "rb") as b:
        assert a.read() == b.read()


# -- the readers --------------------------------------------------------------

HLO = '''
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(worker_step)/forward_backward/jvp(JoyAILM)/JoyAILM.hidden/layer_1/mla/attn/q_b/dot_general" stack_frame_id=3}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(worker_step)/forward_backward/transpose(jvp(JoyAILM))/JoyAILM.hidden/mtp/mtp_block/mla/attn/o/dot_general"}
  %ragged-dot-none.4 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %sort.7 = s32[8]{0} sort(%p), dimensions={0}, metadata={op_name="sort"}
  %multiply_add_fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(worker_step)/update/add"}
  %copy.9 = f32[8]{0} copy(%p)
  %flash_attention_fwd.2 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(worker_step)/forward_backward/jvp(JoyAILM)/JoyAILM.hidden/layer_1/mla/attn/flash_attention_fwd/pallas_call"}
}
'''


class _Run:
    """What a reader is handed, with a synthetic traced slice: two step
    runs of 10 ms on device 0, each holding the instructions of ``HLO``."""

    def __init__(self, cell, with_trace=True):
        self.cell = cell
        self.images_per_device_step = 4
        self.peak = cell.peak("TPU v5 lite")
        self.edges = ({"t": 0.0}, {"t": 1.0, "moe_load_max_over_mean": 1.4,
                                   "packing_waste": 0.0137})
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


SCOPE_READERS = {"mla.device_ms": 3.0, "mtp.device_ms": 1.0,
                 "moe.experts_ms": 1.5, "moe.route_ms": 0.5,
                 "optimizer.device_ms": 3.0}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_a_scope_reader_charges_each_instruction_once(name, kept, capsys):
    """``mtp`` goes before ``mla`` (the module's attention is the
    module's), instructions whose path XLA lost go by their name, and what
    has no name at all is ``other``."""
    read = spec.load_module("layer_metrics", name, spec.BENCH_DIR).read
    assert read(_Run(_cell())) == pytest.approx(SCOPE_READERS[name])
    assert "other 0.250" in capsys.readouterr().out
    assert read(_Run(_cell(), with_trace=False)) is None
    hlo_scopes.KEPT.clear()      # a program whose driver kept no text
    assert read(_Run(_cell())) is None


def test_a_kernels_roofline_counts_its_calls(kept):
    read = spec.load_module("layer_metrics", "flash_attention_fwd_roofline",
                            spec.BENCH_DIR).read
    ops = spec.load_module("ops_count", "joyai_llm_flash", spec.BENCH_DIR)
    operations, _bytes = ops.flash_attention_fwd_cost(
        _cell().config["architecture"], 4)
    # two calls of 1 ms each in the slice
    assert read(_Run(_cell())) == pytest.approx(
        100.0 * 2 * (operations / 197e12) / 2e-3)
    assert read(_Run(_cell(), with_trace=False)) is None
    none = spec.load_module("layer_metrics",
                            "flash_attention_bwd_dq_roofline",
                            spec.BENCH_DIR).read
    assert none(_Run(_cell())) is None       # no such event in the slice


def test_the_counter_readers_read_the_last_edge():
    run = _Run(_cell(), with_trace=False)
    for name, want in (("moe.load_max_over_mean", 1.4),
                       ("data.packing_waste", 0.0137)):
        assert spec.load_module("layer_metrics", name,
                                spec.BENCH_DIR).read(run) == want
    run.edges = ({"t": 0.0}, {"t": 1.0})      # an older program's edge
    assert spec.load_module("layer_metrics", "data.packing_waste",
                            spec.BENCH_DIR).read(run) is None


@pytest.mark.parametrize("name", [
    "trainer.dispatch_ms", "trainer.epoch_end_host_ms", "trainer.input_ms",
    "device.idle_named_share", "device.step_period_ms_max"])
def test_an_lm_reader_is_the_accepted_reading_under_its_own_driver(name):
    accepted = spec.load_module("layer_metrics", name, spec.BENCH_DIR)
    ours = spec.load_module("layer_metrics", "lm." + name, spec.BENCH_DIR)
    assert accepted.DRIVERS == ("sync_mesh",)
    assert ours.DRIVERS == ("sync_mesh_tokens",)
    assert (ours.UNIT, ours.BETTER, ours.SOURCE, ours.LAYER, ours.MOVES) == (
        accepted.UNIT, accepted.BETTER, accepted.SOURCE, accepted.LAYER,
        accepted.MOVES)
    run = _Run(_cell(), with_trace=False)
    run.edges = ({"t": 0.0, "dispatch_n": 0, "dispatch_sum_s": 0.0},
                 {"t": 1.0, "dispatch_n": 4, "dispatch_sum_s": 0.008})
    run.delta = lambda key: run.edges[1][key] - run.edges[0][key]
    assert ours.read(run) == accepted.read(run)


def test_the_five_accepted_entries_list_the_image_cells_and_no_other():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    image = ["resnet18-sync-1chip", "vit-b16-sync-1chip",
             "vit-b16-sync-4chip"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("trainer.dispatch_ms", "trainer.epoch_end_host_ms",
                 "trainer.input_ms", "device.idle_named_share",
                 "device.step_period_ms_max"):
        assert by_name[name]["workloads"] == image
        assert by_name["lm." + name]["workloads"] == [CELL]
    owed = _cell().per_layer
    assert "trainer.input_ms" not in owed and "lm.trainer.input_ms" in owed
    assert {"step.device_ms", "step_roofline", "device.idle_share",
            "entry.compile_s"} <= set(owed)
    assert [w["chips"] for w in bench["workloads"]] == [1, 1, 4, 1]


#: the comparison's readings on the chip (my chip runs, PR 28, seeds
#: 2147485101-3 through benchmarks/run.py): the trainer's step against the
#: float32 reference, and the reference computed in bf16 against the same
CHIP_SOUND = [
    {"loss_rel": 5.573e-05, "logits_max": 0.02124, "grad_l2_worst": 0.1014,
     "grad_l2_median": 0.01016, "update_l2": 0.1212,
     "rule_l2_worst": 6.04e-06, "bias_moved": 0.0007813,
     "routing_moved": 0.007013},
    {"loss_rel": 5.929e-05, "logits_max": 0.01001, "grad_l2_worst": 0.03664,
     "grad_l2_median": 0.01035, "update_l2": 0.1453,
     "rule_l2_worst": 5.435e-06, "bias_moved": 0.0007813,
     "routing_moved": 0.007048},
    {"loss_rel": 9.082e-07, "logits_max": 0.02047, "grad_l2_worst": 0.05465,
     "grad_l2_median": 0.01028, "update_l2": 0.1408,
     "rule_l2_worst": 5.683e-06, "bias_moved": 0.0,
     "routing_moved": 0.006873}]
CHIP_CONTROL = [
    {"loss_rel": 0.0005561, "logits_max": 0.02463, "grad_l2_worst": 0.27,
     "grad_l2_median": 0.01002, "update_l2": 0.211,
     "rule_l2_worst": 1.325e-06, "bias_moved": 0.001563,
     "routing_moved": 0.02354},
    {"loss_rel": 0.002078, "logits_max": 0.02347, "grad_l2_worst": 0.1637,
     "grad_l2_median": 0.01157, "update_l2": 0.1988,
     "rule_l2_worst": 1.186e-06, "bias_moved": 0.004687,
     "routing_moved": 0.0179},
    {"loss_rel": 0.001882, "logits_max": 0.0258, "grad_l2_worst": 0.2475,
     "grad_l2_median": 0.01206, "update_l2": 0.241,
     "rule_l2_worst": 1.108e-06, "bias_moved": 0.0,
     "routing_moved": 0.01983}]


def test_the_cells_limits_pass_the_chips_sound_readings_and_reject_its_control():
    """Each limit has room on both sides of what the chip read: twice the
    largest sound reading still passes where precision moves the reading
    (``loss_rel``) and half again where it hardly does; the control fails
    by ``loss_rel`` and by ``routing_moved`` in every seed."""
    driver = spec.load_module("drivers", "sync_mesh_tokens", spec.BENCH_DIR)
    limits = _cell().config["reference_limits"]
    assert set(limits) == set(CHIP_SOUND[0])
    for found in CHIP_SOUND:
        assert driver.within(found, limits)
        assert driver.within({k: 1.5 * v for k, v in found.items()}, limits)
    for found in CHIP_CONTROL:
        assert not driver.within(found, limits)
        assert found["loss_rel"] > 2 * limits["loss_rel"]
        assert found["routing_moved"] > 1.15 * limits["routing_moved"]
    # what an unchanged state, a zero gradient, a decayed gain read
    for key, reads in (("update_l2", 1.0), ("grad_l2_median", 1.0),
                       ("grad_l2_worst", 1.0), ("bias_moved", 1.0),
                       ("rule_l2_worst", 0.1)):
        assert limits[key] < 0.7 * reads


def test_the_cell_says_how_it_has_to_learn_and_match():
    driver = spec.load_module("drivers", "sync_mesh_tokens", spec.BENCH_DIR)
    config = _cell().config
    assert set(config["learned"]) == {"min_loss_drop"}
    limits = config["reference_limits"]
    good = {k: 0.0 for k in limits}
    assert driver.within(dict(good, worst_tensor="x"), limits)
    for k in limits:
        assert not driver.within(dict(good, **{k: limits[k] * 1.01 + 1e-9}),
                                 limits)
        assert not driver.within(dict(good, **{k: float("nan")}), limits)
    with pytest.raises(ValueError):
        driver.within(good, {"unknown_reading": 1.0})
