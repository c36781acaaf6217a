"""The one traffic generator: reads a mix's parameter file
(``traffic/<mix>.json``) and turns it, with a configuration's graph and the
run's seed, into the requests a driver sends.

Every seed gets the same amount of work: the same number of requests, the
same batch sizes, and (open loop) the same set of inter-arrival gaps, drawn
once from a fixed stream and put in another order by the seed.
"""

from __future__ import annotations

import json
import random
from pathlib import Path
from urllib.parse import urlencode

HERE = Path(__file__).resolve().parent

#: the canonical inter-arrival gaps come from this stream whatever the seed
_ARRIVAL_STREAM = 20260927


def load_mix(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if "driver" not in mix:
        raise ValueError(f"{path}: a traffic mix names its driver")
    return mix


def skewed_objects(seed: int, n_objects: int, n: int, skew: dict):
    """``n`` object indices below ``n_objects`` for ``{"kind": "zipf", "theta":
    t}``: rank r (from 1) is drawn with weight r**-t (t = 0 is uniform), over
    a ranking of the objects shuffled by the seed."""
    import numpy as np

    rng = np.random.Generator(np.random.PCG64(seed))
    if skew.get("kind") != "zipf":
        raise ValueError(f"unknown skew kind {skew.get('kind')!r}")
    weights = np.arange(1, n_objects + 1, dtype=np.float64) ** -float(skew["theta"])
    cum = np.cumsum(weights)
    ranks = np.searchsorted(cum, rng.random(n) * cum[-1], side="right")
    ranking = rng.permutation(n_objects)
    return ranking[np.minimum(ranks, n_objects - 1)].tolist()


def arrival_offsets(seed: int, rate: float, seconds: float) -> list[float]:
    """Poisson arrivals at ``rate`` over ``seconds``: exactly
    ``round(rate * seconds)`` offsets whose gaps are exponential draws from a
    fixed stream, scaled to fill the window, in an order shuffled by the
    seed. Completions never feed back (open loop)."""
    n = max(1, round(rate * seconds))
    fixed = random.Random(_ARRIVAL_STREAM)
    gaps = [fixed.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    random.Random(seed).shuffle(gaps)
    out, t = [], 0.0
    for gap in gaps:
        t += gap * scale
        out.append(t)
    return out


def check_path(query) -> str:
    """``GET /check`` target, as the SDK's ``RelationTuple.to_url_query``
    encodes it."""
    ns, obj, rel, sid = query
    return "/check?" + urlencode(
        [("namespace", ns), ("object", obj), ("relation", rel), ("subject_id", sid)]
    )


def batch_body(queries) -> bytes:
    """``POST /check/batch`` body, as the SDK's ``batch_check`` sends it."""
    return json.dumps(
        {"tuples": [
            {"namespace": ns, "object": obj, "relation": rel, "subject_id": sid}
            for ns, obj, rel, sid in queries
        ]}
    ).encode()
