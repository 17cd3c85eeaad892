"""What every traffic loop shares: the scene and the search parameters
of its configuration, and the record of each timed unit (a frame, a
step or a call) that the comparison reads once the window has closed."""
from __future__ import annotations

import dataclasses

from .names import load_module
from .rng import rng_for


@dataclasses.dataclass
class Record:
    """One unit's points (host or device), its search result as the
    timed path produced it, and, where the path drops what its grid
    cannot hold, the points dropped (a device scalar); None where the
    path recovers them itself. ``queries`` are the rows the result
    answers, where they are not the unit's own points."""

    points: object
    result: object
    overflow: object = None
    queries: object = None


class LoopBase:
    unit_name = "unit"

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.types import SearchParams
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        s = config["search"]
        self.params = SearchParams(radius=float(s["radius"]), k=int(s["k"]),
                                   mode=s["mode"],
                                   knn_window=s.get("knn_window",
                                                    "heuristic"))
        scene = dict(config["scene"])
        self._generate = load_module("scenes", scene.pop("kind")).generate
        self._scene_args = scene
        self.records: list[Record] = []

    def scene(self, *stream: int, **overrides):
        """The scene drawn from ``(seed, *stream)``; ``overrides`` replace
        the configuration's scene parameters."""
        return self._generate(rng_for(self.seed, *stream),
                              **{**self._scene_args, **overrides})

    def plan_spec(self):
        """A frozen grid spec, planned by the program's policy for a grid
        that outlasts many frames (``repro.core.session_grid_spec``) over
        the sensor's calibration frame, drawn from the configuration's
        ``calibration_stream``: the same for every seed."""
        from repro.core import session_grid_spec
        frame = self._generate(
            rng_for(*self.config["calibration_stream"]), **self._scene_args)
        return session_grid_spec(frame, self.params.radius)

    def sizes(self) -> tuple[int, int, int]:
        """(points, queries, K) of one unit; here, of a loop whose units
        self-query, so that its queries are its points."""
        n = int(self.records[0].points.shape[0])
        return n, n, self.params.k

    def notes(self) -> dict:
        """Counts of the window that the run prints on an earlier line."""
        return {}

    def close(self) -> None:
        """Stop whatever the loop started."""
