"""One closed-loop reader of finished cells, in a process of its own.

The harness starts one of these per client of a ``cell_reads`` mix, so
the readers, like figure scripts and dashboards, share no interpreter
with the daemon. Protocol, one JSON or word line each way:

    stdin   {"url", "cells", "traffic", "seed", "client", "seconds",
             "timeout"}
    stdout  ready
    stdin   go
    stdout  {"latencies_s", "failed_at", "answers", "detail"}

After ``go`` it reads ``GET /cell`` for ``seconds`` (its own clock),
one read at a time, the cells drawn from the mix's stream for this
client. ``latencies_s`` holds every read in order, ``failed_at`` the
indices of the reads that raised, and ``answers`` each distinct
``[cell index, record or null, count]``, so that every answer is
compared without sending each one back.
"""

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from chipbench import traffic  # noqa: E402


def main() -> int:
    from repro.core.warpsim.service import SweepClient

    job = json.loads(sys.stdin.readline())
    cells = [tuple(c) for c in job["cells"]]
    stream = traffic.read_stream(job["traffic"], len(cells), job["seed"],
                                 job["client"])
    client = SweepClient(job["url"], timeout=float(job["timeout"]))
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1
    latencies, failed_at, detail = [], [], ""
    answers = {}
    t_end = time.perf_counter() + float(job["seconds"])
    while time.perf_counter() < t_end:
        i = next(stream)
        m, b, s = cells[i]
        t1 = time.perf_counter()
        try:
            rec = dataclasses.asdict(client.cell(b, machine=m, seed=s))
        except Exception as e:  # noqa: BLE001 — a failed read is counted
            rec = None
            failed_at.append(len(latencies))
            detail = detail or f"{type(e).__name__}: {e}"
        latencies.append(time.perf_counter() - t1)
        key = (i, json.dumps(rec, sort_keys=True))
        got = answers.get(key)
        if got is None:
            answers[key] = [i, rec, 1]
        else:
            got[2] += 1
    print(json.dumps({"latencies_s": latencies, "failed_at": failed_at,
                      "answers": list(answers.values()),
                      "detail": detail}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
