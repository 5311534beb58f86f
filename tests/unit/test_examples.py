"""Example/benchmark scripts smoke tests.

The reference's integration tier ran its example case files end-to-end
per strategy (SURVEY.md §4); here each script runs as a subprocess on a
small simulated CPU mesh with tiny sizes.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


pytestmark = pytest.mark.slow

def run_script(rel_path, *args, timeout=240):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": REPO,
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, rel_path), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    return proc.stdout


def test_linear_regression():
    out = run_script("examples/linear_regression.py", "--steps", "6")
    assert "loss=" in out


def test_image_classifier():
    out = run_script("examples/image_classifier.py", "--steps", "4",
                     "--batch-size", "16")
    assert "loss=" in out


def test_sentiment_classifier_partitioned_ps():
    out = run_script("examples/sentiment_classifier.py", "--steps", "4",
                     "--strategy", "PartitionedPS", "--vocab-size", "1000")
    assert "loss=" in out


def test_lm1b_parallax():
    out = run_script("examples/lm1b_train.py", "--steps", "4",
                     "--vocab-size", "2000")
    assert "loss=" in out


def test_benchmark_imagenet_tiny():
    out = run_script("examples/benchmark/imagenet.py", "--model", "resnet18",
                     "--preset", "tiny", "--train-steps", "4",
                     "--log-steps", "2", "--warmup-steps", "1")
    assert "examples_per_sec_final" in out
    assert "resnet18/AllReduce" in out


def test_benchmark_imagenet_per_step_loop():
    """--steps-per-loop 1 keeps the legacy per-step timed loop (true
    per-step latency percentiles via the prefetching DataLoader)."""
    out = run_script("examples/benchmark/imagenet.py", "--model", "resnet18",
                     "--preset", "tiny", "--train-steps", "4",
                     "--log-steps", "2", "--warmup-steps", "1",
                     "--steps-per-loop", "1")
    assert "examples_per_sec_final" in out
    assert "step_ms_p50" in out          # per-step stat, not window-derived
    assert "steps_per_loop" not in out   # fused-path keys absent


def test_benchmark_imagenet_batch_probe(monkeypatch):
    """The self-tuning batch probe (exercised via the candidate override)
    times each size, picks the examples/sec winner, and reports its
    per-chip batch in the JSON headline."""
    monkeypatch.setenv("AUTODIST_TPU_BATCH_CANDIDATES", "1,2")
    out = run_script("examples/benchmark/imagenet.py", "--model",
                     "resnet18", "--preset", "tiny", "--train-steps",
                     "2", "--log-steps", "2", "--warmup-steps", "1",
                     "--json", timeout=300)
    # both probes must SUCCEED (the failure form prints "failed:")
    assert len([l for l in out.splitlines()
                if l.startswith("# probe batch") and "ex/s" in l]) == 2
    assert not [l for l in out.splitlines()
                if l.startswith("# probe batch") and "failed" in l]
    import json as _json
    headline = _json.loads(
        [l for l in out.splitlines() if '"metric"' in l][-1])
    assert headline["batch_per_chip"] in (1, 2)


def test_benchmark_bert_tiny_flash(tmp_path):
    out = run_script("examples/benchmark/bert.py", "--preset", "tiny",
                     "--train-steps", "4", "--log-steps", "2",
                     "--warmup-steps", "1", "--flash-attention",
                     "--benchmark-log-dir", str(tmp_path))
    assert "MFU" in out
    assert (tmp_path / "metric.log").exists()


def test_benchmark_ncf_tiny():
    out = run_script("examples/benchmark/ncf.py", "--preset", "tiny",
                     "--train-steps", "4", "--log-steps", "2",
                     "--warmup-steps", "1")
    assert "ncf/AllReduce" in out


def test_long_context_sequence_parallel():
    out = run_script("examples/long_context.py", "--steps", "2",
                     "--seq-len", "64", "--seq-parallel", "4",
                     "--hidden", "32", "--layers", "1", timeout=300)
    assert "long-context" in out and "sp=4" in out


def test_long_context_ring_flash():
    out = run_script("examples/long_context.py", "--steps", "2",
                     "--seq-len", "64", "--seq-parallel", "4",
                     "--hidden", "32", "--layers", "1", "--flash",
                     timeout=300)
    assert "attn=flash" in out and "sp=4" in out


def test_pipeline_train_interleaved():
    out = run_script("examples/pipeline_train.py", "--steps", "3",
                     "--virtual-stages", "2", "--microbatches", "2",
                     "--hidden", "16", "--batch", "16", timeout=300)
    assert "virtual=2" in out and "bubble" in out and "loss=" in out


def test_pipeline_train_auto_search():
    """--auto-search on a simulated two-slice topology: the search
    report prints (counts, per-level frontier, winner knob string) and
    the elected plan trains."""
    out = run_script("examples/pipeline_train.py", "--steps", "3",
                     "--stages", "2", "--hidden", "16", "--batch", "16",
                     "--auto-search", "--num-slices", "2", timeout=300)
    assert "raw configs" in out and "pruned by dominance" in out
    assert "auto-search winner: dcn2_" in out and "loss=" in out


def test_moe_train_expert_parallel():
    out = run_script("examples/moe_train.py", "--steps", "3",
                     "--experts", "8", "--layers", "1", "--hidden", "32",
                     "--vocab", "64", "--seq-len", "16", "--batch", "16",
                     timeout=300)
    assert "experts over" in out and "aux=" in out
