"""Shared set-up of the benchmark's tests.

``test_bench_trace.test_every_per_layer_metric_reads_the_trace`` checks
every per-layer metric of ``BENCHMARK.json`` against one synthetic trace
built from the program's scopes as they stood when that test was
written. The search kernel's stage scopes (``repro.search.*``) came
later, so the fixture below adds one operation under each stage scope
that a metric reads to the trace that test builds: the stage metrics are
then held to the same check as the others (a number where their scope
ran, nothing where no operation carries a scope).
"""
import pytest

STAGE_OPS = [
    ["fusion.21", "jit(f)/repro.execute_plan/while/body/"
     "repro.search.window_gather/dynamic-slice", 0.25],
    ["fusion.22", "jit(f)/repro.execute_plan/while/body/"
     "repro.search.row_gather/gather", 0.5],
]


@pytest.fixture(autouse=True)
def _stage_scopes_on_the_synthetic_trace(request, monkeypatch):
    if request.node.originalname != \
            "test_every_per_layer_metric_reads_the_trace":
        return
    plain = request.module.synthetic

    def synthetic():
        tr = plain()
        tr.hlo_ops += [list(o) for o in STAGE_OPS]
        return tr

    monkeypatch.setattr(request.module, "synthetic", synthetic)
