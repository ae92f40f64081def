"""The control for ``correct``: the reference matcher with its
injectivity rule dropped, put in the program's place.

    python3 bench/control.py --workload human.closed8 --requests 400 --seeds 11 12 13

For each seed it builds the cell's data graph and query pool as a run
does, answers the first ``--requests`` queries of the pool with the
control, and puts every answer through the run's own checks (every
answer against the reference, not a sample). It prints, per seed, how
many answers the checks call wrong: the upper reading of the
``wrong_answers`` limit. The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, run    # noqa: E402


def control_reading(spec: dict, seed: int, requests: int) -> dict:
    data = run.build_data(spec["config"])
    pool, _ = run.build_queries(data, spec["traffic"])
    limit = int(spec["config"]["server_args"]["limit"])
    didx = reference.DataIndex(data)
    records = [{"i": i, "status": "ok"} for i in range(requests)]
    t0 = time.monotonic()
    res = run.check_run(
        data, pool, records, {}, limit, seed,
        answer=lambda q: reference.match(q, didx, limit, injective=False))
    res["seconds"] = time.monotonic() - t0
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    spec = run.load_cell(a.workload)
    for seed in a.seeds:
        res = control_reading(spec, seed, a.requests)
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "checked": res["checked"],
                          "wrong_answers": res["wrong_answers"],
                          "seconds": round(res["seconds"], 3),
                          "first": res["reasons"][:1]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
