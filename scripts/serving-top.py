#!/usr/bin/env python3
"""Text rendering of the serving engine's deep-observability view
(docs/observability.md "Serving observability").

Fetches ``GET /v1/serving`` (+ optionally ``/v1/serving/requests``) from a
running service and prints a `top`-style dashboard — occupancy, page-pool
and fragmentation state, speculative accept rate, recent step cadence, and
a per-request table. ``--watch N`` refreshes every N seconds until
interrupted, like fleet-top.

    python scripts/serving-top.py [--url http://localhost:50081]
        [--requests N] [--steps N] [--watch SECONDS]
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time

import httpx


def fmt_ms(ms: float | None) -> str:
    if ms is None:
        return "-"
    if ms < 1000:
        return f"{ms:.1f}ms"
    return f"{ms / 1000:.2f}s"


def render_summary(snap: dict) -> str:
    lines = []
    if not snap.get("attached"):
        lines.append(
            "serving: monitor wired, no engine attached "
            "(ApplicationContext.attach_serving_engine)"
        )
        return "\n".join(lines)
    batcher = snap.get("batcher", {})
    totals = snap.get("totals", {})
    active = batcher.get("active_rows", 0)
    max_batch = batcher.get("max_batch", 0) or 1
    lines.append(
        f"serving: occupancy={active}/{batcher.get('max_batch', 0)}"
        f" ({active / max_batch:.0%})"
        f"  prefilling={batcher.get('prefilling_rows', 0)}"
        f"  queue_depth={snap.get('queue_depth', '-')}"
        f"  finished={totals.get('finished', 0)}"
        f"  rejected={totals.get('rejected', 0)}"
        f"  requeued={totals.get('requeued', 0)}"
        f"  preempted={totals.get('preempted', 0)}"
    )
    kv = snap.get("kv_cache", {})
    if kv:
        lines.append(
            f"kv-cache: pages free={kv.get('pages_free', 0)}"
            f" parked={kv.get('pages_parked', 0)}"
            f" held={kv.get('pages_held', 0)}"
            f" shared={kv.get('pages_shared', 0)}"
            f" /{kv.get('pages_total', 0)}"
            f"  fragmentation={kv.get('fragmentation', 0.0):.1%}"
        )
        prefix = kv.get("prefix", {})
        lines.append(
            "prefix-cache: "
            + (
                f"hit_ratio={prefix.get('hit_ratio', 0.0):.0%}"
                f" ({prefix.get('hits', 0)}/{prefix.get('lookups', 0)}"
                f" lookups, {prefix.get('pages_reused', 0)} pages reused,"
                f" {prefix.get('indexed_pages', 0)} indexed)"
                if prefix.get("enabled", True)
                else "disabled"
            )
        )
    spec = totals.get("spec_accepted", 0) + totals.get("spec_rejected", 0)
    if spec:
        lines.append(
            f"speculative: accept_rate={totals.get('spec_accept_ratio', 0.0):.0%}"
            f" ({totals.get('spec_accepted', 0)}/{spec} draft tokens)"
        )
    return "\n".join(lines)


def fmt_bytes(n: float | None) -> str:
    if n is None:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024
    return "-"


def render_accelerator(snap: dict | None) -> str:
    """The compile/HBM pane off ``GET /v1/accelerator``
    (docs/observability.md "Accelerator observability")."""
    if not snap:
        return ""
    compile_ = snap.get("compile", {})
    by_trigger = compile_.get("by_trigger", {})
    mesh = snap.get("mesh") or {}
    lines = [
        f"accelerator: mesh={mesh.get('shape', '1')}"
        f"  compiles={compile_.get('total', 0)}"
        f" (first_call={by_trigger.get('first_call', 0)},"
        f" retrace={by_trigger.get('retrace', 0)})"
    ]
    memory = snap.get("memory", {})
    for dev in memory.get("devices", []):
        est = " (estimated)" if dev.get("estimated") else ""
        lines.append(
            f"  hbm {dev.get('device', '-')}:"
            f" live={fmt_bytes(dev.get('live_bytes'))}"
            f" peak={fmt_bytes(dev.get('peak_bytes'))}"
            f" limit={fmt_bytes(dev.get('limit_bytes'))}{est}"
        )
    recent = compile_.get("recent", [])
    if recent:
        lines.append(
            f"  {'SEQ':>4} {'TRIGGER':<10} {'WALL':>8} "
            f"{'FUNCTION':<24} SIGNATURE"
        )
        for c in recent:
            lines.append(
                f"  {c.get('seq', 0):>4} {c.get('trigger', '-'):<10} "
                f"{fmt_ms(c.get('duration_ms')):>8} "
                f"{c.get('function', '-'):<24} {c.get('signature', '-')}"
            )
    return "\n".join(lines)


def render_steps(snap: dict) -> str:
    steps = snap.get("steps", {})
    last = steps.get("last", [])
    lines = [
        f"steps: {steps.get('recorded', 0)} recorded,"
        f" {steps.get('retained', 0)} retained"
    ]
    if not last:
        return lines[0]
    header = (
        f"  {'SEQ':>6} {'WALL':>8} {'ROWS':>4} {'PRE':>3} {'DEC':>4} "
        f"{'PTOK':>5} {'SPEC+':>5} {'SPEC-':>5} {'PG+':>4} {'PG-':>4} "
        f"{'FREE':>5}"
    )
    lines.append(header)
    for s in last:
        lines.append(
            f"  {s.get('seq', 0):>6} {fmt_ms(s.get('duration_ms')):>8} "
            f"{s.get('active_rows', 0):>4} {s.get('prefilling_rows', 0):>3} "
            f"{s.get('decode_tokens', 0):>4} {s.get('prefill_tokens', 0):>5} "
            f"{s.get('spec_accepted', 0):>5} {s.get('spec_rejected', 0):>5} "
            f"{s.get('pages_allocated', 0):>4} {s.get('pages_released', 0):>4} "
            f"{s.get('free_pages', 0):>5}"
        )
    # the steps' host time by phase (the serve.step.* spans), where the
    # records carry it: medians over the steps shown, in the order they ran
    phases: dict[str, list[float]] = {}
    for s in last:
        for phase, ms in (s.get("phase_ms") or {}).items():
            phases.setdefault(phase, []).append(ms)
    if phases:
        lines.append("  phase p50: " + fmt_medians(phases))
    # and the blocking admissions that rode on them (the serve.admit.*
    # spans; a drain inside one is land and land_*): what one cost, whole
    # and by phase
    admitted: dict[str, list[float]] = {}
    for s in last:
        for a in s.get("admissions") or ():
            admitted.setdefault("admit", []).append(a["duration_ms"])
            for phase, ms in a["phase_ms"].items():
                admitted.setdefault(phase, []).append(ms)
    if admitted:
        lines.append(
            f"  admit p50 ({len(admitted['admit'])}): " + fmt_medians(admitted)
        )
    return "\n".join(lines)


def fmt_medians(by_phase: dict[str, list[float]]) -> str:
    return "  ".join(
        f"{phase} {fmt_ms(statistics.median(ms))}"
        for phase, ms in by_phase.items()
    )


def render_requests(rows: list[dict]) -> str:
    lines = ["", f"requests (newest first, {len(rows)}):"]
    header = (
        f"  {'REQ':>5} {'STATE':<7} {'FINISH':<10} {'PTOK':>5} {'OTOK':>5} "
        f"{'PAGES':>5} {'PFX':>3} {'RQ':>2} {'TTFT':>8} {'WALL':>8}  TRACE"
    )
    lines.append(header)
    lines.append("  " + "-" * (len(header) - 2))
    for r in rows:
        lines.append(
            f"  {r.get('request_id', '-'):>5} "
            f"{'live' if r.get('active') else 'done':<7} "
            f"{(r.get('finish') or '-'):<10} "
            f"{r.get('prompt_tokens', 0):>5} {r.get('output_tokens', 0):>5} "
            f"{r.get('pages', 0):>5} {r.get('prefix_hit_pages', 0):>3} "
            f"{r.get('requeues', 0):>2} {fmt_ms(r.get('ttft_ms')):>8} "
            f"{fmt_ms(r.get('duration_ms')):>8}  {r.get('trace_id', '-')}"
        )
    if not rows:
        lines.append("  (no requests recorded)")
    return "\n".join(lines)


def render_once(
    client: httpx.Client, base: str, requests: int, steps: int
) -> None:
    resp = client.get(f"{base}/v1/serving", params={"steps": steps})
    if resp.status_code == 501:
        print("serving-top: no serving monitor wired into this server")
        return
    snap = resp.raise_for_status().json()
    print(render_summary(snap))
    if snap.get("attached"):
        print(render_steps(snap))
    # Compile/HBM pane: tolerate servers predating /v1/accelerator.
    accel_resp = client.get(
        f"{base}/v1/accelerator", params={"recent": min(steps, 8)}
    )
    if accel_resp.status_code == 200:
        pane = render_accelerator(accel_resp.json())
        if pane:
            print(pane)
    if requests > 0:
        rows = (
            client.get(
                f"{base}/v1/serving/requests", params={"limit": requests}
            )
            .raise_for_status()
            .json()["requests"]
        )
        print(render_requests(rows))


def main() -> int:
    parser = argparse.ArgumentParser(
        description="Render GET /v1/serving as a text dashboard."
    )
    parser.add_argument("--url", default="http://localhost:50081")
    parser.add_argument(
        "--requests",
        type=int,
        default=10,
        metavar="N",
        help="show the newest N per-request records (0 = none)",
    )
    parser.add_argument(
        "--steps",
        type=int,
        default=8,
        metavar="N",
        help="show the last N step records (0 = none)",
    )
    parser.add_argument(
        "--watch",
        type=float,
        default=0,
        metavar="SECONDS",
        help="refresh every N seconds until interrupted (0 = one shot)",
    )
    args = parser.parse_args()
    base = args.url.rstrip("/")
    try:
        with httpx.Client(timeout=10.0) as client:
            while True:
                try:
                    render_once(client, base, args.requests, args.steps)
                except httpx.HTTPError as e:
                    print(
                        f"serving-top: cannot reach {base}: {e}",
                        file=sys.stderr,
                    )
                    if args.watch <= 0:
                        return 1
                if args.watch <= 0:
                    return 0
                time.sleep(args.watch)
                print(f"\n--- {time.strftime('%H:%M:%S')} ---")
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
