"""A whole run with the look for a chip skipped (``--rehearse``: the toy
size on the CPU) and the timed path broken underneath must print
``correct: false``; unbroken it prints ``correct: true``."""
import json

import numpy as np

import run as bench


def _last_line(capsys, argv):
    rc = bench.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


TRAIN = ["--workload", "bert-base-mlm.1chip", "--seed", "7", "--seconds",
         "0.5", "--rehearse"]
SERVE = ["--workload", "gpt2-large-postln.closed-loop", "--seed", "7",
         "--seconds", "1", "--rehearse"]


def test_training_sound(capsys):
    rc, line, _ = _last_line(capsys, TRAIN)
    assert rc == 0 and line["correct"] is True
    assert line["counts"]["steps"] > 0


def _fused(batches) -> bool:
    """The call is a window of several fused steps: the program the
    measured window drives.  The faults below live in that path alone."""
    import jax

    return jax.tree.leaves(batches)[0].shape[0] > 1


def test_training_window_returns_state_unchanged(capsys, monkeypatch):
    """A window of fused steps reports its losses but hands back the
    state it was given: the parameters never move."""
    from autodist_tpu.runner import DistributedRunner

    real = DistributedRunner.run_steps

    def frozen(self, batches, **kw):
        import jax

        if not _fused(batches):
            return real(self, batches, **kw)
        keep = jax.tree.map(lambda x: x.copy(), self.state)
        metrics = real(self, batches, **kw)
        self.state = keep
        return metrics

    monkeypatch.setattr(DistributedRunner, "run_steps", frozen)
    rc, line, out = _last_line(capsys, TRAIN)
    assert rc == 1 and line["correct"] is False
    assert any("delta_norm_gap" in l and "FAILED" in l for l in out)


def test_training_window_leaves_out_part_of_the_batch(capsys, monkeypatch):
    """In a window of fused steps the second half of every batch's rows
    is the first half again: each step trains on half the rows it was
    handed."""
    from autodist_tpu.runner import DistributedRunner

    real = DistributedRunner.run_steps

    def halved(self, batches, **kw):
        def fold(x):
            x = np.array(x)
            h = x.shape[1] // 2
            x[:, h:] = x[:, :h]
            return x
        import jax

        if _fused(batches):
            batches = jax.tree.map(fold, batches)
        return real(self, batches, **kw)

    monkeypatch.setattr(DistributedRunner, "run_steps", halved)
    rc, line, out = _last_line(capsys, TRAIN)
    assert rc == 1 and line["correct"] is False
    assert any("loss_gap" in l and "FAILED" in l for l in out)


def test_training_window_repeats_its_first_step(capsys, monkeypatch):
    """A window of fused steps feeds every step the first step's batch:
    the steps after the first never see their own rows."""
    from autodist_tpu.runner import DistributedRunner

    real = DistributedRunner.run_steps

    def repeated(self, batches, **kw):
        import jax

        if _fused(batches):
            batches = jax.tree.map(
                lambda x: np.repeat(np.asarray(x)[:1], len(x), axis=0),
                batches)
        return real(self, batches, **kw)

    monkeypatch.setattr(DistributedRunner, "run_steps", repeated)
    rc, line, out = _last_line(capsys, TRAIN)
    assert rc == 1 and line["correct"] is False
    assert any("loss_gap" in l and "FAILED" in l for l in out)


def test_serving_sound(capsys):
    from harness import loader

    rc, line, _ = _last_line(capsys, SERVE)
    assert rc == 0 and line["correct"] is True
    counts = line["counts"]
    assert counts["requests_completed"] > 0
    # both counts of one window: the tokens of the requests that completed
    # inside it, and the tokens that reached the host inside it.  They
    # differ by the partial request a caller holds at either end.
    mix = loader.sized(loader.read_json("traffic", "closed-loop.json"), True)
    room = mix["callers"] * mix["output_tokens"]["max"]
    assert counts["tokens_delivered_in_window"] > 0
    assert abs(counts["tokens_delivered_in_window"]
               - counts["tokens_generated"]) <= room
    assert list(line)[-1] == "checks"
    assert line["checks"]["logit_gap"]["limit"] > 0


def test_serving_token_altered_where_it_is_produced(capsys, monkeypatch):
    """Every decode window's tokens come back shifted by one id."""
    from autodist_tpu.serving.engine import ServingEngine

    real = ServingEngine.decode_window

    def shifted(self, active):
        w = real(self, active)
        w.tokens = (w.tokens + 1) % self.cfg.vocab_size
        return w

    monkeypatch.setattr(ServingEngine, "decode_window", shifted)
    rc, line, out = _last_line(capsys, SERVE)
    assert rc == 1 and line["correct"] is False
    assert any("logit_gap" in l and "FAILED" in l for l in out)
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_training_leaves_out_the_exchange_between_chips(capsys, monkeypatch):
    """Four (virtual) chips whose gradient all-reduce returns each chip's
    own gradient: the replicas drift apart.  The four-chip cell is a
    throw-away entry over the one-chip cell's files, added as a later PR
    would add one: data only."""
    from jax import lax

    from harness import loader

    spec = loader.benchmark_spec()
    one = loader.find_cell(spec, "bert-base-mlm.1chip")
    four = dict(one, name="bert-base-mlm.tryout4", chips=4)
    spec["workloads"].append(four)
    for section in ("end_to_end", "per_layer"):
        for m in spec[section]:
            if one["name"] in m.get("workloads", []):
                m["workloads"].append(four["name"])
    monkeypatch.setattr(loader, "benchmark_spec", lambda: spec)
    argv = ["--workload", four["name"], "--seed", "7", "--seconds", "0.5",
            "--rehearse"]
    rc, line, _ = _last_line(capsys, argv)
    assert rc == 0 and line["correct"] is True
    monkeypatch.setattr(lax, "psum", lambda x, axis_name, **kw: x)
    monkeypatch.setattr(lax, "pmean", lambda x, axis_name, **kw: x)
    rc, line, out = _last_line(capsys, argv)
    assert rc == 1 and line["correct"] is False
    assert any("replicas_differ" in l and "FAILED" in l for l in out)
