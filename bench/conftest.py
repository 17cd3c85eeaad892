"""Set-up that extends a harness test written before the graph loop.

``test_bench_harness.test_broken_timed_path_is_not_correct`` runs every
cell of ``BENCHMARK.json`` with faults planted under its timed path, but
its planter (``_plant``) knows only the loops that existed when it was
written. The fixture below teaches it the ``graph_batches`` loop, so the
new cell is held to the same check as the others: a planted fault comes
out not correct. It changes nothing for the cells that test already
knew. It matches the test and its helper by name: a per-loop planter
hook, which each loop file provides, would replace it (PERF.md
section 7).
"""
import numpy as np
import pytest

from bench.lib import names


def _graph_fault(program, fault):
    """``program`` of the graph loop with ``fault`` planted under it: the
    first batch's answers every time, the second half of every cloud's
    rows missing, or every eighth row's nearest id changed."""
    first = []

    def broken(points):
        res = program(points)
        if fault == "stale":
            if not first:
                first.append(res)
            return first[0]
        n = res.counts.shape[1]
        if fault == "half":
            res = type(res)(indices=res.indices.at[:, n // 2:].set(-1),
                            distances2=res.distances2.at[:, n // 2:].set(
                                np.inf),
                            counts=res.counts.at[:, n // 2:].set(0))
        else:
            idx = res.indices
            res = type(res)(indices=idx.at[:, ::8, 0].set(
                (idx[:, ::8, 0] + 1) % n), distances2=res.distances2,
                counts=res.counts)
        return res
    return broken


@pytest.fixture(autouse=True)
def _graph_loop_in_the_harness_tests(request, monkeypatch):
    if request.node.originalname != "test_broken_timed_path_is_not_correct":
        return
    plain = request.module._plant
    real = names.load_module

    def plant(mp, fault):
        plain(mp, fault)
        planted = names.load_module

        def load(kind, loop):
            if kind != "loops" or loop != "graph_batches":
                return planted(kind, loop)
            mod = real(kind, loop)
            build = mod.Loop.build_program
            mod.Loop.build_program = \
                lambda self: _graph_fault(build(self), fault)
            return mod
        mp.setattr(names, "load_module", load)

    monkeypatch.setattr(request.module, "_plant", plant)
