"""``prefill_positions_useful_pct``'s reader: the runner's admitted
prompt tokens over the program's own ``engine/prefill_positions``, and
nothing to read from a program that keeps no such counter (the parent of
the PR that brought it)."""
import pytest

from autodist_tpu import telemetry
from harness import loader

# what the runner's probe keeps of each prefill dispatch of the window:
# (when, requests admitted, prompt tokens admitted)
PREFILLS = [(0.1, 1, 40), (0.2, 2, 100), (0.3, 1, 52)]


@pytest.fixture
def read():
    telemetry.reset()
    yield loader.load_module("metrics", "prefill_positions_useful_pct").read
    telemetry.reset()


def test_admitted_tokens_over_the_programs_positions(read):
    # four one-row dispatches of a 128-position bucket
    telemetry.counter("engine/prefill_rows").inc(4)
    telemetry.counter("engine/prefill_positions").inc(4 * 128)
    assert read({"prefills": PREFILLS}) == pytest.approx(100 * 192 / 512)


def test_a_program_without_the_counter_reads_nothing(read):
    telemetry.counter("serve/tokens").inc(7)      # other counters do not
    assert read({"prefills": PREFILLS}) is None


def test_the_metric_is_declared_for_both_serving_cells():
    spec = loader.benchmark_spec()
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "prefill_positions_useful_pct")
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["layer"] == "serving engine"
    for cell in entry["workloads"]:
        assert entry in loader.metrics_of(spec, "per_layer", cell)
        assert cell in next(m for m in spec["end_to_end"]
                            if m["name"] == entry["moves"])["workloads"]
