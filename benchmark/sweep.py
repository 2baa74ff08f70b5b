#!/usr/bin/env python3
"""Find the knee of a cell's traffic, once, on the chip (not part of a check).

    python3 benchmark/sweep.py --workload <cell> --rates 1,1.5,2,2.5,3 [--step_s 20]

One process, one server set up as ``run.py`` sets it up; the offered rate
steps upward and each step prints offered and finished requests a second,
the queue at mid-step and at its end, and the latencies. A step holds when,
over its last two thirds, it finished at least 0.97 of the requests that came
due there, its queue did not grow from mid-step to the end, and no request
failed; the knee is the highest step that holds. Each step waits for its own
requests before the next begins. PERF.md keeps the tables, and says which
cell runs below its knee and which above.
"""

import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

from benchmark import run  # noqa: E402


def sweep(b, rates, step_s: float, seed: int) -> None:
    from benchmark import client, measure, traffic

    pct = lambda v, q: measure.percentile(v, q) if v else float("nan")
    run.log(b.say, "sweep: offered/s finished/s_late_2/3 due_late_2/3 "
                   "queued_mid queued_end active_end ttft_p50_ms ttft_p90_ms "
                   "tpot_p90_ms failed drain_s holds")
    for k, rate in enumerate(rates):
        p = {**b.params, "arrivals": {**b.params["arrivals"], "rate_per_s": rate}}
        sched = traffic.build_schedule(p, seed + 104729 * (k + 1), step_s)
        n_before = len(b.driver.records)
        t0 = time.perf_counter() + 0.05
        t1 = t0 + step_s
        mid = {}
        timer = threading.Timer(step_s / 2, lambda: mid.update(b.engine.stats()))
        timer.start()
        threads = b.driver.run_open(sched, t0, step_s)
        time.sleep(max(0.0, t1 - time.perf_counter()))
        end = b.engine.stats()
        client.join_all(threads, 180.0)
        drain_s = time.perf_counter() - t1
        recs = b.driver.records[n_before:]
        rows = measure.rows_from(recs, {}, {})
        ttft = [(r.t_first - r.t_due) * 1e3 for r in rows if r.ok]
        tpot = [(r.t_last - r.t_first) / (r.tokens - 1) * 1e3
                for r in rows if r.ok and r.tokens >= 2]
        lo = t0 + step_s / 3
        due_late = sum(1 for r in recs if lo <= r.t_due < t1)
        done_late = sum(1 for r in recs if r.status == "ok"
                        and r.t_done is not None and lo <= r.t_done < t1)
        failed = sum(not r.ok for r in rows)
        grew = end.get("queued", 0) > max(mid.get("queued", 0), 1)
        holds = done_late >= 0.97 * due_late and not grew and not failed
        run.log(b.say, f"sweep: {len(recs) / step_s:.3f} "
                       f"{done_late / (t1 - lo):.3f} {due_late / (t1 - lo):.3f} "
                       f"{mid.get('queued')} {end.get('queued')} "
                       f"{end.get('active_rows')} {pct(ttft, 50):.1f} "
                       f"{pct(ttft, 90):.1f} {pct(tpot, 90):.2f} {failed} "
                       f"{drain_s:.1f} {holds}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step_s", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=20260930)
    ap.add_argument("--rehearsal", action="store_true")
    o = ap.parse_args(argv)
    opts = run.build_parser().parse_args(
        ["--workload", o.workload, "--seed", str(o.seed), "--seconds", "1"]
        + (["--rehearsal"] if o.rehearsal else []))
    b = run.set_up(opts, run.process_start())
    if isinstance(b, int):
        return b
    try:
        sweep(b, [float(r) for r in o.rates.split(",")], o.step_s, o.seed)
    finally:
        run.shut_down(b)
    return 0


if __name__ == "__main__":
    sys.exit(main())
