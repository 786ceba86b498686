"""examples/albert data pipeline: self-contained corpus tokenizer + BERT-style
masking statistics, and the sampler fallback chain; 2-peer smoke run of the
actual run_trainer.py recipe."""

import os
import re
import subprocess
import sys

import numpy as np
import pytest
from swarm_utils import read_child_until

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples", "albert"))

from data import MASK, NUM_SPECIAL, TextMLMDataset, make_batch_sampler  # noqa: E402


@pytest.fixture
def corpus(tmp_path):
    path = tmp_path / "corpus.txt"
    words = ["alpha", "beta", "gamma", "delta", "epsilon"] * 400
    rng = np.random.RandomState(0)
    rng.shuffle(words)
    path.write_text(" ".join(words))
    return str(path)


def test_text_mlm_dataset_masking(corpus):
    dataset = TextMLMDataset(corpus, vocab_size=64, seq_len=32, mask_prob=0.15)
    rng = np.random.RandomState(1)
    batch = dataset.sample_batch(rng, batch_size=64)
    assert batch["input_ids"].shape == batch["labels"].shape == batch["mlm_mask"].shape == (64, 32)
    assert batch["labels"].min() >= NUM_SPECIAL  # only real words in this corpus
    assert batch["labels"].max() < 64

    # unselected positions are untouched
    untouched = ~batch["mlm_mask"]
    np.testing.assert_array_equal(batch["input_ids"][untouched], batch["labels"][untouched])

    # BERT 80/10/10: ~80% of selected positions are [MASK]; ~15% selected overall
    selected = batch["mlm_mask"]
    rate = selected.mean()
    assert 0.10 < rate < 0.20, rate
    mask_fraction = (batch["input_ids"][selected] == MASK).mean()
    assert 0.7 < mask_fraction < 0.9, mask_fraction
    # and some positions differ from the label without being [MASK] (random 10%)
    changed = (batch["input_ids"] != batch["labels"]) & selected & (batch["input_ids"] != MASK)
    assert changed.sum() > 0


def test_make_batch_sampler_chain(corpus):
    from hivemind_tpu.models import AlbertConfig

    config = AlbertConfig.tiny(max_position=32)
    real = make_batch_sampler(config, seq_len=32, dataset_path=corpus, seed=3)
    batch = real(8)
    assert batch["input_ids"].shape == (8, 32)

    synthetic = make_batch_sampler(config, seq_len=32, seed=3)
    batch = synthetic(4)
    assert batch["input_ids"].shape == (4, 32)
    assert set(batch) == {"input_ids", "labels", "mlm_mask"}


def test_shared_vocab_across_peers(tmp_path, corpus):
    """Two peers with DIFFERENT corpora get an identical token mapping through the
    shared vocab file (the collaborative-training requirement)."""
    vocab_path = str(tmp_path / "vocab.txt")
    first = TextMLMDataset(corpus, vocab_size=64, seq_len=16, vocab_path=vocab_path)

    other_corpus = tmp_path / "other.txt"
    other_corpus.write_text("gamma beta zeta " * 200)  # different corpus, different stats
    second = TextMLMDataset(str(other_corpus), vocab_size=64, seq_len=16, vocab_path=vocab_path)
    assert first.vocab == second.vocab  # mapping came from the shared file

    import pytest as _pytest

    from data import make_batch_sampler

    with _pytest.raises(ValueError, match="hf_tokenizer"):
        from hivemind_tpu.models import AlbertConfig

        make_batch_sampler(AlbertConfig.tiny(max_position=16), 16, hf_tokenizer="bert-base-uncased")


def test_run_trainer_causal_model_smoke():
    """--model causal trains the decoder-only family through the same recipe: a
    single peer advances solo epochs and exits cleanly."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    script = os.path.join(repo, "examples", "albert", "run_trainer.py")
    env = {**os.environ, "PYTHONPATH": repo}
    run = subprocess.run(
        [sys.executable, script, "--model", "causal", "--tiny", "--platform", "cpu",
         "--run_id", "causal_smoke", "--max_steps", "6", "--target_batch_size", "32",
         "--batch_size", "16", "--seq_len", "64", "--matchmaking_time", "0.5", "--seed", "0"],
        stderr=subprocess.PIPE, text=True, cwd=repo, timeout=180, env=env,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    assert re.search(r"training finished after 6 steps at epoch (\d+)", run.stderr), run.stderr[-2000:]


@pytest.mark.slow  # ~50 s; the one-process trainer path stays covered by
# test_run_trainer_causal_model_smoke, and two-peer swarm training by test_optimizer.py
def test_run_trainer_two_peer_smoke():
    """The flagship recipe end-to-end: two run_trainer.py processes (tiny config,
    synthetic data) form a swarm, advance epochs together, and exit cleanly after
    max_steps (regression: the trainer used to hang on background threads)."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    script = os.path.join(repo, "examples", "albert", "run_trainer.py")
    common = [
        sys.executable, script, "--tiny", "--platform", "cpu",
        "--run_id", "smoke", "--max_steps", "16", "--target_batch_size", "64",
        "--batch_size", "16", "--seq_len", "64", "--matchmaking_time", "1.0",
    ]
    env = {**os.environ, "PYTHONPATH": repo}
    first = subprocess.Popen(
        common + ["--seed", "0"], stderr=subprocess.PIPE, text=True, cwd=repo, env=env
    )
    try:
        head = read_child_until(first, r"--initial_peers \S+\s", timeout=120, stream="stderr")
        assert "--initial_peers" in head, f"first peer never announced its address: {head[-2000:]}"
        maddr = re.search(r"--initial_peers (\S+)", head).group(1)

        # the monitor joins as a non-training observer and must see swarm progress
        monitor_script = os.path.join(repo, "examples", "albert", "run_training_monitor.py")
        monitor = subprocess.Popen(
            [sys.executable, monitor_script, "--run_id", "smoke", "--initial_peers",
             maddr, "--refresh_period", "2.0", "--max_reports", "1"],
            stderr=subprocess.PIPE, text=True, cwd=repo, env=env,
        )

        # generous deadlines: at the tail of a full-suite run this test shares
        # the one core with compile-heavy neighbors and can legitimately take
        # several minutes (it passes alone in ~1) — a timeout here is a flake,
        # not a hang signal
        second = subprocess.run(
            common + ["--seed", "1", "--initial_peers", maddr],
            stderr=subprocess.PIPE, text=True, cwd=repo, timeout=420, env=env,
        )
        first_err = first.communicate(timeout=240)[1]
        logs = head + (first_err or "") + (second.stderr or "")
        assert second.returncode == 0, logs[-3000:]
        assert first.returncode == 0, logs[-3000:]
        finished = re.findall(r"training finished after 16 steps at epoch (\d+)", logs)
        assert len(finished) == 2, logs[-3000:]
        # 2 peers x 16 steps x 16 samples = 512 samples = 8 virtual epochs of 64:
        # both peers must have transitioned epochs collaboratively at least twice
        assert all(int(epoch) >= 2 for epoch in finished), finished

        monitor_err = monitor.communicate(timeout=60)[1]
        assert monitor.returncode == 0, monitor_err[-2000:]
        assert re.search(r"epoch \d+: \d+ peers, \d+ samples accumulated", monitor_err), (
            monitor_err[-2000:]
        )
    finally:
        if first.poll() is None:
            first.kill()
        if "monitor" in locals() and monitor.poll() is None:
            monitor.kill()
