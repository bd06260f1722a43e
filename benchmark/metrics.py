"""From a run's records to its end-to-end numbers. Each metric is what
``BENCHMARK.json`` says it is, over all the work and all the time of the
window; nothing here drops a request to quiet a number."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .stats import percentile, summary

TPOT_MIN_TOKENS = 32


class Records:
    """Per-request records of one run, joined from the client's side
    (``Drive.sent``, results) and the program's stamps (``timing``)."""

    def __init__(self, system, drive) -> None:
        self.drive = drive
        self.t0, self.t1 = drive.window
        self.rows: List[Dict[str, Any]] = []
        for s in drive.sent:
            timing = system.timings.get(s.rid) or {}
            first = timing.get("first_token") or drive.inflight_first_token.get(s.rid)
            self.rows.append(
                {
                    "rid": s.rid,
                    "due": s.due,
                    "sent": s.sent,
                    "prompt_tokens": s.request.prompt_tokens,
                    "output_tokens": s.request.output_tokens,
                    "first_token": first or None,
                    "received": system.results.get(s.rid),
                    **{
                        k: timing.get(k)
                        for k in ("enqueued", "admitted", "prefill_start",
                                  "last_token", "finished", "preempt_count",
                                  "completion_tokens")
                    },
                }
            )

    def due_in_window(self) -> List[Dict[str, Any]]:
        return [r for r in self.rows if self.t0 <= r["due"] < self.t1]

    def finished_in_window(self) -> List[Dict[str, Any]]:
        return [
            r for r in self.rows
            if r["finished"] is not None and self.t0 <= r["finished"] < self.t1
        ]

    def ttft_ms(self) -> List[float]:
        """First-token stamp minus the instant the request was due, over
        the requests due inside the window. One with no first token counts
        as the worst: the time from its due instant to the end of the run."""
        worst = max(
            [r["first_token"] or 0.0 for r in self.rows] + [self.t1]
        )
        return [
            ((r["first_token"] or worst) - r["due"]) * 1e3
            for r in self.due_in_window()
        ]

    def tpot_ms(self) -> List[float]:
        out = []
        for r in self.finished_in_window():
            n = r["completion_tokens"] or 0
            if n >= TPOT_MIN_TOKENS and r["first_token"] and r["last_token"]:
                out.append((r["last_token"] - r["first_token"]) / (n - 1) * 1e3)
        return out

    def lateness_ms(self) -> List[float]:
        return [(r["sent"] - r["due"]) * 1e3 for r in self.rows]


def end_to_end(generator: str, records: Records, setup_s: float) -> Dict[str, Optional[float]]:
    """Every end-to-end metric this kind of traffic has something for. A
    fixed job's throughput is ``job_tok_s``, the job's received tokens over
    first submit to last result, and not ``out_tok_s``, a window's tokens
    over the window: one job's length is set by how admission fell, a
    window's rate by the device, and a name has one bound."""
    d = records.drive
    out: Dict[str, Optional[float]] = {"setup_s": setup_s}
    if generator == "open_loop":
        ttft = records.ttft_ms()
        out["ttft_p50_ms"] = percentile(ttft, 50)
    if generator in ("open_loop", "closed_loop"):
        out["tpot_p50_ms"] = percentile(records.tpot_ms(), 50)
        tokens = d.stats1["generated_tokens"] - d.stats0["generated_tokens"]
        out["out_tok_s"] = tokens / (records.t1 - records.t0)
    if generator == "fixed_job":
        tokens = sum(
            r["completion_tokens"] or 0 for r in records.rows if r["received"]
        )
        out["job_tok_s"] = tokens / d.job_seconds if d.job_seconds else None
    return out


def failures(generator: str, records: Records) -> Dict[str, int]:
    """attempted / failed, as the last line reports them."""
    d = records.drive
    if generator == "open_loop":
        rows = records.due_in_window()
        failed = sum(1 for r in rows if not r["first_token"])
    elif generator == "closed_loop":
        rows = records.finished_in_window()
        failed = 0
    else:
        rows = records.rows
        failed = d.unfinished
    return {"attempted": len(rows), "failed": failed}


def earlier_lines(generator: str, records: Records) -> Dict[str, Any]:
    """Percentiles with their sample counts, for the run's earlier lines."""
    out = {
        "generator_lateness_ms": summary(records.lateness_ms()),
        "tpot_ms": summary(records.tpot_ms()),
    }
    if generator == "open_loop":
        out["ttft_ms"] = summary(records.ttft_ms())
    return out
