"""Real-data example paths (reference: ``examples/`` are CI smoke targets,
``.buildkite/pipeline.yml``).  Each example's real loader runs end-to-end on
a generated on-disk fixture: IDX files (mnist), an ImageFolder tree
(imagenet), official-schema SQuAD JSON (squad)."""

import gzip
import json
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # real-data example runs + driver dryruns (subprocess, minutes)

from helpers import REPO_ROOT

EXAMPLES = os.path.join(REPO_ROOT, "examples")


def _run_example(script, args, timeout=300):
    """Run an example pinned to a 1-device CPU backend (examples have no
    platform override of their own)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT
    r = subprocess.run(
        [sys.executable, script, *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_flax_strategy_example():
    """The three-call Flax adoption path trains and exits through to_flax."""
    out = _run_example(
        os.path.join(EXAMPLES, "flax_strategy", "main.py"),
        ["--algorithm", "gradient_allreduce", "--steps", "12", "--batch", "32"],
    )
    assert "final step 12" in out
    losses = [float(l.split("loss")[1]) for l in out.splitlines() if "loss" in l]
    assert losses[-1] < losses[0], out  # it actually learned


def test_mnist_real_idx(tmp_path):
    rng = np.random.RandomState(0)
    imgs = (rng.rand(256, 28, 28) * 255).astype(np.uint8)
    labels = rng.randint(0, 10, 256).astype(np.uint8)
    with gzip.open(tmp_path / "train-images-idx3-ubyte.gz", "wb") as f:
        f.write(struct.pack(">HBB", 0, 8, 3) + struct.pack(">III", 256, 28, 28)
                + imgs.tobytes())
    with open(tmp_path / "train-labels-idx1-ubyte", "wb") as f:
        f.write(struct.pack(">HBB", 0, 8, 1) + struct.pack(">I", 256)
                + labels.tobytes())
    out = _run_example(
        os.path.join(EXAMPLES, "mnist", "main.py"),
        ["--data-dir", str(tmp_path), "--epochs", "1", "--batch-size", "64"],
    )
    assert "256 samples (real)" in out


def test_imagenet_real_folder(tmp_path):
    PIL = pytest.importorskip("PIL.Image")
    rng = np.random.RandomState(0)
    for c in range(2):
        d = tmp_path / f"class_{c}"
        d.mkdir()
        for i in range(4):
            arr = (rng.rand(40 + 8 * c, 48, 3) * 255).astype(np.uint8)
            PIL.fromarray(arr).save(d / f"img_{i}.jpeg")
        (d / "README.txt").write_text("not an image")  # must be skipped
    out = _run_example(
        os.path.join(EXAMPLES, "imagenet", "main.py"),
        ["--data-dir", str(tmp_path), "--arch", "vgg16", "--image-size", "32",
         "--batch-size", "2", "--steps", "2"],
    )
    assert "8 images, 2 classes" in out


def test_squad_real_json(tmp_path):
    pytest.importorskip("tokenizers")
    ctx = "The quick brown fox jumps over the lazy dog near the river bank."
    data = {"data": [{"title": "t", "paragraphs": [{
        "context": ctx,
        "qas": [
            {"id": str(k), "question": f"What does the fox jump over ({k})?",
             "answers": [{"text": "the lazy dog", "answer_start": ctx.index("the lazy dog")}]}
            for k in range(24)
        ],
    }]}]}
    path = tmp_path / "train.json"
    path.write_text(json.dumps(data))
    out = _run_example(
        os.path.join(EXAMPLES, "squad", "main.py"),
        ["--data", str(path), "--batch-size", "2", "--steps", "2", "--seq", "64"],
    )
    assert "24 SQuAD features" in out


@pytest.mark.parametrize("n_devices", [16])
def test_dryrun_multichip_wider_than_test_mesh(n_devices):
    """The driver calls dryrun_multichip with arbitrary device counts; guard
    the path at a width larger than the suite's 8-device mesh (fresh
    subprocess: the simulated device count is fixed at jax init)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO_ROOT
    r = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__; __graft_entry__.dryrun_multichip({n_devices})"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO_ROOT,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_llama_pretrain_real_text(tmp_path):
    """Char-LM on a real UTF-8 corpus fixture through the dp x tp x sp
    example (8-device sim inside the subprocess)."""
    text = ("To be, or not to be, that is the question:\n" * 80)
    corpus = tmp_path / "corpus.txt"
    corpus.write_text(text, encoding="utf-8")
    env_extra = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT, **env_extra)
    r = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "llama_pretrain", "main.py"),
         "--data", str(corpus), "--dp", "2", "--tp", "2", "--sp", "2",
         "--steps", "8", "--seq", "32", "--batch", "8"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    lines = [l for l in r.stdout.splitlines() if l.startswith("final:")]
    assert lines, r.stdout
    # loss must improve on real text over a few steps
    parts = lines[0].split("loss")[1].split("->")
    assert float(parts[1]) < float(parts[0]), lines[0]
