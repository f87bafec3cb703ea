"""Compile for a TPU v5e that is described, not attached.

The TPU's compiler is installed in the sandbox and compiles for a chip given
by its topology alone (guide ``on-chip-measurement``, section 2, rehearsal
3). Nothing runs, so these tests say nothing of results or times; they pin
what the compiled ViT-B/16 train step *is*: which instructions, which
layouts, how many bytes of temporaries. All compiles for the described chip
live in this one file, behind fixtures: the worker that is handed the file
loads the TPU's library, no other does.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributed_parameter_server_for_ml_training_tpu.ops import (
    attention as at)
from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
    short_attention as sa)

#: ``memory_analysis().temp_size_in_bytes`` of the same step with
#: ``dense_core`` (PR 27's compile of the parent for the described chip).
DENSE_TEMP_BYTES = 9.31e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without a chip; keep these out of it."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """The tests run with JAX_PLATFORMS=cpu, so the dispatch would take its
    CPU branch: steer the backend probe here, never through an option."""
    monkeypatch.setattr(at, "_on_tpu", lambda: True)
    assert sa.INTERPRET is False


def _vit_b16_state(sharding):
    """The benchmark's ViT configuration (224 px, 1000 classes, bf16, SGD)
    as shapes on ``sharding``: nothing is initialised."""
    from distributed_parameter_server_for_ml_training_tpu.models import (
        get_model)
    from distributed_parameter_server_for_ml_training_tpu.train.optimizers \
        import server_sgd
    from distributed_parameter_server_for_ml_training_tpu.train.train_state \
        import create_train_state

    model = get_model("vit_b16", num_classes=1000, dtype=jnp.bfloat16,
                      axis_name="data", image_size=224)
    state = jax.eval_shape(lambda: create_train_state(
        model, jax.random.PRNGKey(0), server_sgd(0.03),
        input_shape=(1, 224, 224, 3)))
    return jax.tree_util.tree_map(
        lambda x: _shaped(x.shape, x.dtype, sharding), state)


def _shaped(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("b,t,h,d", [
    (4, 197, 12, 64),        # ViT-B/16 @224: blocks overhang 197 rows
    (1, sa.MAX_T, 12, 64),   # the longest sequence the dispatch sends
    (2, sa.MAX_T, 2, 128),   # one head a group
])
def test_short_attention_kernels_compile(b, t, h, d, one_chip,
                                         no_compile_cache):
    """Forward and backward fit the VMEM they ask for (``MAX_T``'s
    comment) and lower with no unaligned slice."""
    qkv = _shaped((b, t, 3 * h * d), jnp.bfloat16, one_chip)
    text = jax.jit(jax.grad(lambda x: jnp.sum(
        sa.short_attention(x, h).astype(jnp.float32)))).lower(
            qkv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "short_attention_fwd" in text and "short_attention_bwd" in text


def test_vit_b16_step_holds_the_fused_core(topo, as_on_tpu,
                                           no_compile_cache):
    """The benchmark's ViT cells' program (``worker_step``, batch 128,
    224 px, 1000 classes, bf16, SGD) for one v5e: 24 kernel calls, the
    scores never in HBM, no layout copy around the kernels, and at least
    3 GB fewer temporaries than with ``dense_core``."""
    from distributed_parameter_server_for_ml_training_tpu.parallel.sync_dp \
        import make_sync_dp_step

    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    replicated, split = (NamedSharding(mesh, P()),
                         NamedSharding(mesh, P("data")))
    step = make_sync_dp_step(mesh, compression="bf16", augment=True)
    compiled = step.lower(
        _vit_b16_state(replicated),
        _shaped((128, 224, 224, 3), jnp.uint8, split),
        _shaped((128,), jnp.int32, split),
        _shaped((2,), jnp.uint32, replicated)).compile()
    text = compiled.as_text()

    assert text.count('custom_call_target="tpu_custom_call"') == 24
    # the instructions carry the kernels' names, not the flax scope's
    # (``block_5.2``): a profile's breakdown then has two rows, not twelve
    names = set(re.findall(
        r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))
    assert names == {"short_attention_fwd", "short_attention_bwd"}
    assert "[128,12,197,197]" not in text
    # The entry computation's instructions are the device's kernels. (The
    # layout changes XLA fuses into a matmul's operand read stay inside
    # nested fused computations and cost no pass of their own.)
    entry = text[text.index("\nENTRY "):]
    copies = re.findall(r"= (\S+) copy\(", entry)
    assert 0 < len(copies) < 20, copies  # augmentation's; 92 with dense_core
    for shape in ("[128,197,2304]", "[128,197,3,12,64]",
                  "[128,197,1,12,64]"):
        assert not [c for c in copies if shape in c], (shape, copies)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= DENSE_TEMP_BYTES - 3e9, temp


def test_vit_b16_evaluation_on_four_chips_holds_the_fused_core(
        topo, as_on_tpu, no_compile_cache):
    """``SyncTrainer``'s evaluation of a state replicated over a four-chip
    mesh. As a plain ``jit`` it was a program for GSPMD, which refuses a
    kernel call ("Mosaic kernels cannot be automatically partitioned"); as
    the ``shard_map`` ``make_sync_dp_eval_step`` builds it lowers, with the
    forward kernel in each of the 12 blocks."""
    from distributed_parameter_server_for_ml_training_tpu.parallel.sync_dp \
        import make_sync_dp_eval_step

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    replicated = NamedSharding(mesh, P())
    text = make_sync_dp_eval_step(mesh).lower(
        _vit_b16_state(replicated),
        _shaped((128, 224, 224, 3), jnp.uint8, replicated),
        _shaped((128,), jnp.int32, replicated)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 12
    assert "[128,12,197,197]" not in text


# -- the decoder LM's cell (joyai-flash-ep16-sync-1chip) ----------------------

#: what one v5e's 16 GB leave a program (arguments + temporaries)
LM_MEMORY_LIMIT = 15.7e9


@pytest.fixture(scope="module")
def lm_programs(topo):
    """The cell's step and evaluation programs (``worker_step`` /
    ``eval_step`` with the LM task, the ``ep16`` preset, 4 sequences of
    4,096 tokens, bf16, AdamW) compiled for one described v5e; about a
    minute and a half, once for the tests below."""
    from distributed_parameter_server_for_ml_training_tpu.parallel.sync_dp \
        import make_sync_dp_eval_step, make_sync_dp_step
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig
    from distributed_parameter_server_for_ml_training_tpu.train.tasks import (
        LMTask)

    class Data:
        vocab_size, seq_len = 16160, 4096

    was_tpu, at._on_tpu = at._on_tpu, lambda: True
    was_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        replicated, split = (NamedSharding(mesh, P()),
                             NamedSharding(mesh, P("data")))
        task = LMTask("ep16")
        cfg = DistributedConfig(model="joyai_llm_flash", learning_rate=3e-4)
        model = task.make_model(cfg, Data, jnp.bfloat16, "data")
        state = jax.eval_shape(lambda: task.init_state(
            model, jax.random.PRNGKey(0), task.make_optimizer(cfg), Data))
        state = jax.tree_util.tree_map(
            lambda x: _shaped(x.shape, x.dtype, replicated), state)
        step = make_sync_dp_step(mesh, compression="none", task=task).lower(
            state, _shaped((4, 4098), jnp.int32, split),
            _shaped((2,), jnp.uint32, replicated)).compile()
        evaluation = make_sync_dp_eval_step(mesh, task).lower(
            state, _shaped((4, 4098), jnp.int32, replicated)).compile()
    finally:
        at._on_tpu = was_tpu
        jax.config.update("jax_enable_compilation_cache", was_cache)
    return state, step, evaluation


def test_lm_step_fits_one_chip_and_keeps_no_score_matrix(lm_programs):
    state, step, _evaluation = lm_programs
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(state.params))
    assert n + 5 * 256 == 680_441_088
    memory = step.memory_analysis()
    # parameters and the two float32 moments are the arguments, donated;
    # the float32 gradients are among the temporaries
    assert memory.argument_size_in_bytes >= 12 * n
    assert memory.alias_size_in_bytes >= 12 * n
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < LM_MEMORY_LIMIT), memory
    text = step.as_text()
    assert not re.search(r"\[\d+,32,4096,4096\]", text)
    assert not re.search(r"\[128,4096,4096\]", text)
    names = set(re.findall(
        r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))
    # the three flash kernels (six blocks: 12 forward runs with the
    # recomputation, 6 and 6 backward) and XLA's grouped-matmul kernels
    assert {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"} <= names
    assert any(name.startswith("ragged-dot") for name in names)
    for kernel, calls in (("flash_attention_fwd", 12),
                          ("flash_attention_bwd_dq", 6),
                          ("flash_attention_bwd_dkv", 6)):
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == calls


def test_lm_evaluation_fits_beside_the_state(lm_programs):
    state, _step, evaluation = lm_programs
    memory = evaluation.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    # the whole state stays resident while the evaluation runs
    assert held + memory.temp_size_in_bytes < LM_MEMORY_LIMIT, memory
    text = evaluation.as_text()
    assert not re.search(r"\[\d+,32,4096,4096\]", text)
    # five blocks: the accuracy is the main head's, so the MTP module's
    # block is dead code in this program
    assert len(re.findall(r"%flash_attention_fwd(?:\.\d+)? = ", text)) == 5


# -- the window model's step: 16,384 tokens, a band, grouped queries ------------

@pytest.fixture(scope="module")
def smallthinker_step(topo):
    """The SmallThinker cell's step program (``worker_step`` with the LM
    task, the ``ep4`` preset, 1 sequence of 16,384 tokens, bf16, AdamW)
    compiled for one described v5e; under a minute."""
    from distributed_parameter_server_for_ml_training_tpu.parallel.sync_dp \
        import make_sync_dp_step
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig
    from distributed_parameter_server_for_ml_training_tpu.train.tasks import (
        LMTask)

    class Data:
        vocab_size, seq_len = 37984, 16384

    was_tpu, at._on_tpu = at._on_tpu, lambda: True
    was_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        replicated, split = (NamedSharding(mesh, P()),
                             NamedSharding(mesh, P("data")))
        task = LMTask("ep4")
        cfg = DistributedConfig(model="smallthinker", learning_rate=3e-6)
        model = task.make_model(cfg, Data, jnp.bfloat16, "data")
        state = jax.eval_shape(lambda: task.init_state(
            model, jax.random.PRNGKey(0), task.make_optimizer(cfg), Data))
        state = jax.tree_util.tree_map(
            lambda x: _shaped(x.shape, x.dtype, replicated), state)
        step = make_sync_dp_step(mesh, compression="none", task=task).lower(
            state, _shaped((1, 16386), jnp.int32, split),
            _shaped((2,), jnp.uint32, replicated)).compile()
    finally:
        at._on_tpu = was_tpu
        jax.config.update("jax_enable_compilation_cache", was_cache)
    return state, step


def test_smallthinker_step_fits_one_chip_with_its_band_in_vmem(
        smallthinker_step):
    """What interpret mode cannot show: the three kernels with a window's
    element-indexed band blocks, a group's 7 query heads an inner grid axis
    and, on the global layer, 16,384 rows of K and V (dK/dV: of Q, dO and
    the two row statistics) in VMEM compile for the chip, and the step fits
    its memory."""
    state, step = smallthinker_step
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(state.params))
    assert n == 656_529_920
    memory = step.memory_analysis()
    assert memory.argument_size_in_bytes >= 12 * n
    assert memory.alias_size_in_bytes >= 12 * n
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < LM_MEMORY_LIMIT), memory
    text = step.as_text()
    # no score matrix, of a whole layer or of a band
    assert not re.search(r"\[[\d,]*16384,16384\]", text)
    assert not re.search(r"\[[\d,]*16384,4608\]", text)
    # four blocks: 8 forward runs with the recomputation, 4 and 4 backward
    for kernel, calls in (("flash_attention_fwd", 8),
                          ("flash_attention_bwd_dq", 4),
                          ("flash_attention_bwd_dkv", 4),
                          ("flash_attention_bwd_delta", 4)):
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == calls
    assert re.search(r"%ragged-dot", text)
    # the scopes the cell's readers go by
    for scope in ("attn_window", "attn_full", "moe_route", "moe_experts",
                  "head_loss", "update"):
        assert f"/{scope}/" in text, scope


# -- the hybrid model's step: a chunked scan, latent experts, 2 x 8,192 tokens ----

@pytest.fixture(scope="module")
def nemotron_step(topo):
    """The Nemotron cell's step program (``worker_step`` with the LM task,
    the ``tp8_ep64`` preset, 2 sequences of 8,192 tokens, bf16, AdamW)
    compiled for one described v5e; about a minute."""
    from distributed_parameter_server_for_ml_training_tpu.parallel.sync_dp \
        import make_sync_dp_step
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig
    from distributed_parameter_server_for_ml_training_tpu.train.tasks import (
        LMTask)

    class Data:
        vocab_size, seq_len = 16384, 8192

    was_tpu, at._on_tpu = at._on_tpu, lambda: True
    was_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        replicated, split = (NamedSharding(mesh, P()),
                             NamedSharding(mesh, P("data")))
        task = LMTask("tp8_ep64")
        cfg = DistributedConfig(model="nemotron_h", learning_rate=3e-6)
        model = task.make_model(cfg, Data, jnp.bfloat16, "data")
        state = jax.eval_shape(lambda: task.init_state(
            model, jax.random.PRNGKey(0), task.make_optimizer(cfg), Data))
        state = jax.tree_util.tree_map(
            lambda x: _shaped(x.shape, x.dtype, replicated), state)
        step = make_sync_dp_step(mesh, compression="none", task=task).lower(
            state, _shaped((2, 8194), jnp.int32, split),
            _shaped((2,), jnp.uint32, replicated)).compile()
    finally:
        at._on_tpu = was_tpu
        jax.config.update("jax_enable_compilation_cache", was_cache)
    return state, step


def test_nemotron_step_fits_one_chip_and_keeps_no_state_a_token(
        nemotron_step):
    """The step fits the chip's memory; the scan's largest arrays are a
    chunk's decays (``[2, 64, 16, 128, 128]``) and one state a chunk, never
    a state a token; the attention layer runs the flash kernels (4 query
    heads on one key/value head); the ungated experts' two weight gradients
    a layer go through the accumulate kernel at ``[3,072, 1,024] x [3,072,
    2,688]``, tiles from the shapes; the readers' scopes are in the text."""
    state, step = nemotron_step
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(state.params))
    assert n == 700_862_960
    memory = step.memory_analysis()
    assert memory.argument_size_in_bytes >= 12 * n
    assert memory.alias_size_in_bytes >= 12 * n
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < LM_MEMORY_LIMIT), memory
    text = step.as_text()
    # a state a token would be [2, 8192, 16, 64, 128]; a state a chunk is
    # [.., 64 chunks, 2, 1, 16, 64, 128] in some order of the leading axes
    assert not re.search(r"\[[\d,]*8192,(?:1,)?16,64,128\]", text)
    assert re.search(r"f32\[(?:64,2|2,64),1,16,64,128\]", text)
    assert not re.search(r"\[[\d,]*8192,8192\]", text)       # no scores
    for kernel, calls in (("flash_attention_fwd", 2),
                          ("flash_attention_bwd_dq", 1),
                          ("flash_attention_bwd_dkv", 1),
                          ("flash_attention_bwd_delta", 1)):
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == calls
    calls = re.findall(
        r"%grouped_grad_accumulate(?:\.\d+)? = (\S+?)\{[^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert len(calls) == 2 * 5
    assert set(calls) == {"f32[8,1024,2688]", "f32[8,2688,1024]"}
    assert re.search(r"%ragged-dot", text)
    for scope in ("ssm", "ssm_scan", "attn_full", "moe_route", "moe_experts",
                  "moe_latent", "moe_shared", "head_loss", "update"):
        assert f"/{scope}/" in text, scope


# -- the expert layer's backward pass: weight gradients added in place (PR 35) --

#: ``memory_analysis().temp_size_in_bytes`` of the parent's steps (PR 34's
#: tree compiled for the same described chip), whose backward loop wrote a
#: pass's three weight gradients in bf16 and added them to the whole carry.
#: The JoyAI step's bound has 13 MB of room since PR 37, which keeps 5.6 MB
#: of routing integers from the forward pass (parallel/moe.py:KEEP_ROUTING):
#: the compiler then places 6,541,663,744 bytes of temporaries, 9.7 MB over
#: PR 34's 6,531,915,264, beside 14.2 MB less generated code; on the chip
#: the cell's peak fell by 13.4 MB (PERF.md, PR 37)
PARENT_TEMP_BYTES = {"smallthinker": 5_715_688_448, "joyai": 6_545_000_000}


def _while_bodies(text: str) -> list[str]:
    """The text of every computation some ``while`` runs as its body."""
    bodies = []
    for name in set(re.findall(r"body=%([\w.\-]+)", text)):
        at = text.index(f"\n%{name} (")
        bodies.append(text[at:text.index("\n}\n", at)])
    return bodies


def _whole_carry_fusions(text: str, carries: tuple) -> list[str]:
    """Fusions inside a ``while`` body that make an array of one of the
    shapes ``carries`` from an operand of that same shape: a pass over a
    whole carried expert gradient."""
    found = []
    for body in _while_bodies(text):
        for line in body.splitlines():
            made = re.match(
                r"\s*(?:ROOT )?%[\w.\-]+ = (\S+?)\{\S* fusion\((.*?)\), kind=",
                line)
            if not made or made.group(1) not in carries:
                continue
            for operand in re.findall(r"%([\w.\-]+)", made.group(2)):
                shape = re.search(
                    rf"%{re.escape(operand)} = (\S+?)[{{ ]", body)
                if shape and shape.group(1) == made.group(1):
                    found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("cell", ["smallthinker", "joyai"])
def test_the_backward_loop_adds_weight_gradients_in_place(
        cell, request):
    """Both LM cells' compiled steps: no fusion inside a ``while`` body
    makes a float32 array of a carried expert gradient's shape from an
    operand of that shape (the parent's three ``convert_add_fusion`` a
    pass); ``grouped_grad_accumulate`` is there, three calls a loop, each
    writing into the accumulator it was handed; XLA's ``ragged-dot`` still
    computes the forward pass and ``dx``; the readers' scopes are where
    they were."""
    if cell == "smallthinker":
        _state, step = request.getfixturevalue("smallthinker_step")
        d, layers = 2560, 4
    else:
        _state, step, _evaluation = request.getfixturevalue("lm_programs")
        d, layers = 2048, 5       # four expert blocks and the MTP module's
    carries = (f"f32[16,{d},768]", f"f32[16,768,{d}]")
    text = step.as_text()
    assert not _whole_carry_fusions(text, carries)
    calls = re.findall(
        r"%grouped_grad_accumulate(?:\.\d+)? = (\S+?)\{[^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert len(calls) == 3 * layers and set(calls) == set(carries)
    for line in re.findall(r"%grouped_grad_accumulate(?:\.\d+)? = [^\n]*", text):
        # operand 6 is the carry (after the plan's four arrays, lhs, rhs)
        assert "output_to_operand_aliasing={{}: (6, {})}" in line, line[:300]
        assert "/moe_experts/" in line
    assert any("%grouped_grad_accumulate" in body
               for body in _while_bodies(text))
    assert re.search(r"%ragged-dot", text)
    temp = step.memory_analysis().temp_size_in_bytes
    assert temp <= PARENT_TEMP_BYTES[cell], temp
    for scope in ("moe_route", "moe_experts", "head_loss", "update"):
        assert f"/{scope}/" in text, scope


# -- an expert layer routes once a step (PR 37) ---------------------------------

@pytest.mark.parametrize("cell", ["joyai", "smallthinker", "nemotron"])
def test_the_step_selects_and_sorts_once_an_expert_layer(cell, request):
    """The three decoder cells' compiled steps: a block is recomputed in
    the backward pass but for its routing decisions
    (``parallel/moe.py:KEEP_ROUTING``), so the step holds one selection (on
    the TPU ``top_k`` is a sort of every token's ``E`` scores) and one sort
    of the ``N k`` assignments an expert layer, where the parent held two
    of each. The passes loop is computed again only where a weight gradient
    needs its sum: three ``while`` bodies with grouped matmuls a layer in
    the Nemotron cell (``latent_up``), two in the others."""
    if cell == "joyai":
        _state, step, _evaluation = request.getfixturevalue("lm_programs")
        k, e, layers, loops = 8, 256, 5, 2       # four blocks and the MTP's
    elif cell == "smallthinker":
        _state, step = request.getfixturevalue("smallthinker_step")
        k, e, layers, loops = 6, 64, 4, 2
    else:
        _state, step = request.getfixturevalue("nemotron_step")
        k, e, layers, loops = 22, 512, 5, 3
    text = step.as_text()
    n = 16384
    selections = re.findall(
        rf"= \(f32\[{n},{e}\]\S*, s32\[{n},{e}\]\S*\) sort\(", text)
    sorts = re.findall(
        rf"= \(s32\[{n * k}\]\S*, s32\[{n * k}\]\S*\) sort\(", text)
    assert len(selections) == layers and len(sorts) == layers
    grouped = [body for body in _while_bodies(text) if "%ragged-dot" in body]
    assert len(grouped) == loops * layers
