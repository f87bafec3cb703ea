"""The sync trainer's programs did not move when the code under them did.

``SyncTrainer`` builds its step and evaluation programs through
``parallel/sync_dp.py`` and the model family's task (``train/tasks.py``).
Two refactors were held to the programs of the tree before them:

- the image task: what ``parallel/sync_dp.py`` wrote out for images
  (augmentation, ``standardize``, label cross-entropy, top-1) moved into
  ``ImageTask``; ``resnet18`` and ``vit_b16`` lower as they did before;
- the decoders: the blocks, the LM skeleton and the routed-expert layer
  that the four decoder LMs share moved into ``models/decoder.py``; the
  four decoders at their ``tiny`` presets, built as ``cli train --mode sync
  --model <name>`` builds them, lower as they did before.

The fixture holds, a model and a program, the SHA-256 of
``lower().as_text()`` and of its scope paths: ``as_text(debug_info=True)``
with every location reduced to the operation names it carries (no file,
line, column or Python frame), so that every instruction's name-scope path
is pinned too. It was written by this file's ``__main__`` on the tree
before the change.
"""

import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "sync_programs_lowered_sha256.json")
#: image model -> (image size, classes, per-worker batch, workers)
IMAGE_CASES = {"resnet18": (32, 100, 4, 2), "vit_b16": (224, 1000, 2, 1)}
#: decoder LM at its ``tiny`` preset -> (per-worker batch, workers)
LM_CASES = {"joyai_llm_flash": (2, 2), "smallthinker": (2, 2),
            "nemotron_h": (2, 2), "lfm2_moe": (2, 2)}
CASES = sorted(IMAGE_CASES) + sorted(LM_CASES)
PROGRAMS = ("jit_eval_step", "jit_worker_step")


def _image_lowered(model_name: str) -> dict:
    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        Dataset)
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig, SyncTrainer

    size, classes, batch, workers = IMAGE_CASES[model_name]
    r = np.random.default_rng(0)
    n = batch * workers
    data = Dataset(
        r.integers(0, 255, (n, size, size, 3), dtype=np.uint8),
        (np.arange(n) % classes).astype(np.int32),
        r.integers(0, 255, (n, size, size, 3), dtype=np.uint8),
        (np.arange(n) % classes).astype(np.int32),
        num_classes=classes, synthetic=True)
    trainer = SyncTrainer(data, DistributedConfig(
        num_workers=workers, batch_size=batch, num_classes=classes,
        model=model_name, dtype="bfloat16"))
    images, labels = trainer._shard((data.x_train, data.y_train))
    rng = jax.random.PRNGKey(1)
    return {
        "jit_worker_step": trainer._step.lower(
            trainer.state, images, labels, rng),
        "jit_eval_step": trainer._eval_step.lower(
            trainer.state, jnp.asarray(data.x_test),
            jnp.asarray(data.y_test)),
    }


def _lm_lowered(model_name: str) -> dict:
    import argparse

    from distributed_parameter_server_for_ml_training_tpu.cli import (
        _load_dataset)
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig, SyncTrainer

    batch, workers = LM_CASES[model_name]
    # the data and trainer of ``cli train --mode sync --model <name>``
    data = _load_dataset(argparse.Namespace(model=model_name))
    trainer = SyncTrainer(data, DistributedConfig(
        num_workers=workers, batch_size=batch, model=model_name,
        dtype="bfloat16"))
    (tokens,) = trainer._shard(next(iter(trainer._train_batches(0))))
    (held_out,) = next(iter(trainer._eval_batches()))
    return {
        "jit_worker_step": trainer._step.lower(
            trainer.state, tokens, jax.random.PRNGKey(1)),
        "jit_eval_step": trainer._eval_step.lower(trainer.state, held_out),
    }


def lowered(model_name: str) -> dict:
    """``{program: jax.stages.Lowered}`` of the sync trainer's two programs
    for a model, built exactly as ``SyncTrainer.__init__`` builds them."""
    return (_image_lowered(model_name) if model_name in IMAGE_CASES
            else _lm_lowered(model_name))


_LOC_DEF = re.compile(r"^(#loc\d*) = loc\((.*)\)$", re.M)
_NAMED = re.compile(r'^"((?:[^"\\]|\\.)*)"(?:\((.*)\))?$', re.S)
_FILE = re.compile(r'^"(?:[^"\\]|\\.)*":\d')


def _each_loc(text: str, replace) -> str:
    """``text`` with every ``loc(...)`` replaced by ``replace(inside)``;
    quoted names may hold parentheses."""
    out, i = [], 0
    while (j := text.find("loc(", i)) >= 0:
        k, depth = j + 4, 1
        while depth:
            if text[k] == '"':
                k += 1
                while text[k] != '"':
                    k += 2 if text[k] == "\\" else 1
            elif text[k] == "(":
                depth += 1
            elif text[k] == ")":
                depth -= 1
            k += 1
        out += [text[i:j], replace(text[j + 4:k - 1])]
        i = k
    return "".join(out + [text[i:]])


def scope_paths(text: str) -> str:
    """``text`` (``as_text(debug_info=True)``) with the location
    definitions dropped and every location replaced by the operation names
    it carries, outside in: a source frame (a name around a file position),
    a file position and a call site carry none."""
    defs = dict(_LOC_DEF.findall(text))

    def names(loc: str) -> tuple:
        named = _NAMED.match(defs.get(loc, loc))
        if named is None:                   # a file, a call site, unknown
            return ()
        name, child = named.groups()
        if child is None:
            return (name,)
        if _FILE.match(defs.get(child, child)):     # a Python frame
            return ()
        return (name,) + names(child)

    body = "\n".join(line for line in text.splitlines()
                     if not _LOC_DEF.match(line))
    reduced = _each_loc(body, lambda loc: "loc(%s)" % json.dumps(
        "|".join(names(loc))))
    assert "#loc" not in reduced, "a location this reduction cannot read"
    return reduced


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


@functools.cache
def digests(model_name: str) -> dict:
    """``{program: {"text": sha, "scopes": sha}}``."""
    out = {}
    for program, low in lowered(model_name).items():
        text = low.as_text()
        assert f"@{program}" in text or program[4:] in text
        out[program] = {"text": _sha(text),
                        "scopes": _sha(scope_paths(
                            low.as_text(debug_info=True)))}
    return out


def _want(model_name: str) -> dict:
    with open(FIXTURE) as f:
        return json.load(f)[model_name]


@pytest.mark.parametrize("model_name", CASES)
def test_lowered_programs_equal_the_parents(model_name):
    got, want = digests(model_name), _want(model_name)
    for program in PROGRAMS:
        assert got[program]["text"] == want[program]["text"], \
            f"{model_name} {program} is not the program the parent lowers"


@pytest.mark.parametrize("model_name", CASES)
def test_scope_paths_equal_the_parents(model_name):
    got, want = digests(model_name), _want(model_name)
    for program in PROGRAMS:
        assert got[program]["scopes"] == want[program]["scopes"], \
            f"{model_name} {program}: an instruction's scope path moved"


def test_scope_paths_drop_frames_and_keep_op_names():
    text = "\n".join([
        'module @m {',
        '  %0 = stablehlo.add %a, %b : tensor<f32> loc(#loc3)',
        '  %1 = stablehlo.mul %0, %0 : tensor<f32> loc(#loc4)',
        '  %2 = stablehlo.neg %1 : tensor<f32> loc("shard_map")',
        '  %3 = stablehlo.abs %2 : tensor<f32> loc(callsite(#loc2 at #loc1))',
        '  %4 = stablehlo.exp %3 : tensor<f32> loc("a(b)/exp"(#loc2))',
        '} loc(#loc)',
        '#loc = loc(unknown)',
        '#loc1 = loc("/x/model.py":3:4 to :9)',
        '#loc2 = loc("Block.__call__"(#loc1))',
        '#loc3 = loc("jit(f)/layer_0/mla/add"(#loc2))',
        '#loc4 = loc("jit:"(#loc3))'])
    got = scope_paths(text)
    assert 'loc("jit(f)/layer_0/mla/add")' in got
    assert 'loc("jit:|jit(f)/layer_0/mla/add")' in got
    assert 'loc("shard_map")' in got and "Block.__call__" not in got
    assert 'abs %2 : tensor<f32> loc("")' in got
    assert 'loc("a(b)/exp")' in got
    assert "model.py" not in got and "#loc" not in got
    # a renamed Python function leaves the reduction as it was
    assert scope_paths(text.replace("Block.__call__", "run")) == got


if __name__ == "__main__":   # regenerate: only ever from the parent's tree
    import sys
    out = {m: digests(m) for m in CASES}
    if len(sys.argv) > 1:    # keep the texts too, for a diff by hand
        for m in CASES:
            for program, low in lowered(m).items():
                with open(os.path.join(sys.argv[1], f"{m}.{program}.txt"),
                          "w") as f:
                    f.write(low.as_text())
                with open(os.path.join(sys.argv[1],
                                       f"{m}.{program}.scopes.txt"),
                          "w") as f:
                    f.write(scope_paths(low.as_text(debug_info=True)))
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    with open(FIXTURE, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1))
