"""The ``modelnet.knn_graph`` cell on the CPU: the scene, the loop's
records and sizes, and the comparison catching a batch whose answers are
another cloud's."""
import contextlib
import io
import json
import math
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.lib import harness, names  # noqa: E402
from bench.lib.rng import rng_for  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "modelnet.knn_graph"
SEED = 2**33 + 5


def cell(rehearse=True):
    return harness.resolve_cell(BENCH, CELL, rehearse)


def loop_after(units, rehearse=True):
    c = cell(rehearse)
    loop = names.load_module("loops", c.traffic["loop"]).Loop(
        c.config, c.traffic, SEED)
    try:
        loop.setup()
        for _ in range(units):
            loop.unit()
    finally:
        loop.close()
    return loop, c


def test_scene_is_normalised_then_augmented():
    gen = names.load_module("scenes", "surface_samples").generate
    plain = gen(rng_for(SEED, 0), clouds=8, points=1024, max_primitives=4,
                scale=[1.0, 1.0], shift=0.0)
    assert plain.shape == (8, 1024, 3) and plain.dtype == np.float32
    norms = np.linalg.norm(plain, axis=-1)
    np.testing.assert_allclose(norms.max(axis=1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(plain.mean(axis=1), 0.0, atol=1e-6)
    scene = cell(rehearse=False).config["scene"]
    args = {k: v for k, v in scene.items() if k != "kind"}
    aug = gen(rng_for(SEED, 1), **args)
    assert aug.shape == (32, 1024, 3)
    assert np.abs(aug).max() <= 1.5 * 1.0 + 0.2 + 1e-6
    # the same stream gives the same batch
    np.testing.assert_array_equal(aug, gen(rng_for(SEED, 1), **args))


def test_rehearsal_is_judged_correct():
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                             "--seconds", "0.5", "--trace", "1",
                             "--rehearse"])
    assert rc == 0, err.getvalue()
    lines = out.getvalue().strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["wrong_rows"]["value"] == 0
    # no grid, so nothing it could drop
    assert "grid_overflow" not in line["checks"]
    notes = json.loads(lines[-2].split("; ", 1)[1])
    assert notes["compiles"] == 0


def test_one_record_per_cloud_and_batch_sizes():
    loop, c = loop_after(2)
    scene = c.config["scene"]
    assert len(loop.records) == 2 * scene["clouds"]
    assert loop.sizes() == (scene["clouds"] * scene["points"],
                            scene["clouds"] * scene["points"], 20)
    full = cell(rehearse=False)
    big = names.load_module("loops", full.traffic["loop"]).Loop(
        full.config, full.traffic, SEED)
    assert big.sizes() == (32768, 32768, 20)
    rec = loop.records[3]
    assert np.asarray(rec.result.indices).shape == (scene["points"], 20)
    # each record is its own cloud's rows: the cloud's points answer it
    np.testing.assert_array_equal(np.asarray(rec.result.indices)[:, 0],
                                  np.arange(scene["points"]))
    assert rec.overflow is None


def test_another_clouds_answers_are_caught():
    loop, c = loop_after(2)
    checks, failed = harness.compare(loop, c.config, SEED)
    assert checks["wrong_rows"]["value"] == 0 and failed == 0
    # cloud 1 of the second batch answered with cloud 0's rows: ids of
    # the same range, each a real neighbour set, of the wrong cloud
    clouds = c.config["scene"]["clouds"]
    wrong = loop.records[clouds + 1]
    right = loop.records[clouds]
    wrong.result = right.result
    checks, failed = harness.compare(loop, c.config, SEED)
    assert checks["wrong_rows"]["value"] >= 1 and failed == 1
    per_cloud = math.ceil(int(c.config["sample_rows"]) / len(loop.records))
    assert checks["wrong_rows"]["value"] == per_cloud
