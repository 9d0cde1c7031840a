"""PR 40's per-layer metrics: where the manifest lists them, and that the
readers that were there read the new series (CPU, not tier-1)."""

import pytest

from benchmark import manifest
from benchmark.readers import prom_mean_diff, prom_ratio

STEADY = "als-amazon14-r128-exact.serve-steady"
SESSIONS = "lfm2-24b-a2b-l9.serve-sessions"
BULK = ("als-amazon14-r128-exact.bulk-score", "minicpm-sala-l8.bulk-turns",
        "phi4-mini-flash-l32.bulk-turns-64")
RUN = "pio_host_thread_run_seconds_total"
CLOCK = "pio_host_clock_seconds_total"
SERVE = {"host_python_cpu_cores.serve", "host_runtime_cpu_cores.serve",
         "gc_pause_ms_per_s.serve"}
BULK_HOST = {"host_python_cpu_cores.bulk", "host_runtime_cpu_cores.bulk",
             "gc_pause_ms_per_s.bulk"}
CELLS = [
    (STEADY, SERVE),
    (SESSIONS, SERVE | {"seq_extend_host_ms"}),
    *[(cell, BULK_HOST
       | ({"seq_extend_host_ms.bulk"} if "turns" in cell else set()))
      for cell in BULK],
    ("als-netflix-r64.retrain", set()),
]
NEW = set().union(*(names for _, names in CELLS))


@pytest.mark.parametrize("cell, names", CELLS)
def test_each_cell_lists_the_metrics_that_can_be_read_in_it(cell, names):
    # By name, wherever a later PR's appends leave them in the list.
    assert len(NEW) == 8
    doc = manifest.load()
    listed = {m["name"] for m in manifest.cell(doc, cell).per_layer}
    assert listed & NEW == names


def _ctx(before, after):
    return {"before": before, "after": after, "config": {}}


def test_python_cores_are_run_seconds_over_the_programs_own_clock():
    spec = manifest.layer_metric_spec("host_python_cpu_cores.serve")
    before = {CLOCK: 100.0, RUN + '{role="main"}': 2.0,
              RUN + '{role="batcher"}': 1.0, RUN + '{role="runtime"}': 9.0}
    after = {CLOCK: 140.0, RUN + '{role="main"}': 12.0,
             RUN + '{role="batcher"}': 11.0, RUN + '{role="runtime"}': 99.0,
             RUN + '{role="handler"}': 4.0}      # born in the window
    assert prom_ratio.read(_ctx(before, after), **spec["args"]) \
        == pytest.approx(0.6)
    runtime = manifest.layer_metric_spec("host_runtime_cpu_cores.bulk")
    assert prom_ratio.read(_ctx(before, after), **runtime["args"]) \
        == pytest.approx(2.25)
    # The parent has no clock series: nothing, and no exception.
    assert prom_ratio.read(_ctx({}, {"pio_batch_size_count": 3.0}),
                           **spec["args"]) is None


def test_the_hosts_part_of_a_program_is_the_span_less_its_wait():
    spec = manifest.layer_metric_spec("seq_extend_host_ms.bulk")
    stage = 'pio_dispatch_stage_ms_{}{{stage="{}"}}'
    after = {stage.format("sum", "seq_extend"): 400.0,
             stage.format("count", "seq_extend"): 10.0,
             stage.format("sum", "seq_wait"): 355.0,
             stage.format("count", "seq_wait"): 10.0,
             stage.format("sum", "wait"): 999.0,
             stage.format("count", "wait"): 10.0}
    assert prom_mean_diff.read(_ctx({}, after), **spec["args"]) \
        == pytest.approx(4.5)
