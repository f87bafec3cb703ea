"""The image task did not move when the trainer learned to take a task.

``SyncTrainer`` builds its step and evaluation programs through
``parallel/sync_dp.py``; PR 28 moved what those wrote out for images
(augmentation, ``standardize``, label cross-entropy, top-1) into the image
task (``train/tasks.py``). The lowered programs of ``resnet18`` and
``vit_b16`` must be the text they were on PR 27's tree: the fixture holds
the SHA-256 of each ``lower().as_text()``, taken there with this file's
``__main__`` before the change.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "image_task_lowered_sha256.json")
#: model -> (image size, classes, per-worker batch, workers)
CASES = {"resnet18": (32, 100, 4, 2), "vit_b16": (224, 1000, 2, 1)}


def lowered_texts(model_name: str) -> dict:
    """``{program: text}`` of the sync trainer's two programs for a model,
    built exactly as ``SyncTrainer.__init__`` builds them."""
    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        Dataset)
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig, SyncTrainer

    size, classes, batch, workers = CASES[model_name]
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
            trainer.state, images, labels, rng).as_text(),
        "jit_eval_step": trainer._eval_step.lower(
            trainer.state, jnp.asarray(data.x_test),
            jnp.asarray(data.y_test)).as_text(),
    }


def digests(model_name: str) -> dict:
    return {program: hashlib.sha256(text.encode()).hexdigest()
            for program, text in lowered_texts(model_name).items()}


@pytest.mark.parametrize("model_name", sorted(CASES))
def test_lowered_programs_equal_the_parents(model_name):
    with open(FIXTURE) as f:
        want = json.load(f)[model_name]
    texts = lowered_texts(model_name)
    for program, text in texts.items():
        assert f"@{program}" in text or program[4:] in text
        assert hashlib.sha256(text.encode()).hexdigest() == want[program], \
            f"{model_name} {program} is not the program PR 27's tree lowers"


if __name__ == "__main__":   # regenerate: only ever from the parent's tree
    import sys
    out = {m: digests(m) for m in sorted(CASES)}
    if len(sys.argv) > 1:    # keep the texts too, for a diff by hand
        for m in CASES:
            for program, text in lowered_texts(m).items():
                with open(os.path.join(sys.argv[1], f"{m}.{program}.txt"),
                          "w") as f:
                    f.write(text)
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    with open(FIXTURE, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1))
