"""Run one cell once: set up, measure, check, print the result line.

The order is fixed: refuse anything but the chips the cell asks for; set
up the compile cache; make the weights on the device from the seed; warm
up the cell's own shapes through the engine; measure for ``--seconds``
(with ``--trace 1``, under the profiler for its first ``TRACE_S``); read
the peak memory; free the program's state; compare a sample of what was
served with the plain reference; print.
"""
from __future__ import annotations

import json
import math
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

from . import check, device, spec, traffic
from .traffic import Mix

TRACE_S = 6.0        # traced part of a --trace 1 window
NO_LIMIT = 1 << 30   # admission room for a whole window


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class RunData:
    """What a per-layer metric's reader may read."""
    arch: Dict[str, Any]
    mix: Mix
    peaks: Dict[str, float]
    records: List[Any]                 # drive.Record of the window
    trace: Optional[Any] = None        # trace.Trace of the traced part
    busy_s: float = 0.0
    window_s: float = 0.0

    def idle_share_pct(self) -> Optional[float]:
        if self.trace is None or not self.trace.ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def end_to_end(mix: Mix, records, t0: float,
               seconds: float) -> Dict[str, float]:
    """The user-visible numbers of a window opened at ``t0``.

    A closed loop's rate is the generated tokens of the window over its
    length: each answered session's tokens in the share of its time, from
    sent to answered, that lies inside the window.  The sessions still
    running at the close are waited for, so a run's last sessions count as
    far as the window saw them.  An open loop's tail is over every request
    due in the window, a miss counting as +inf."""
    out: Dict[str, float] = {}
    if mix.loop == "closed":
        close = t0 + seconds
        tokens = sum(r.tokens.size * max(0.0, min(r.done, close) - max(r.due, t0))
                     / (r.done - r.due) for r in records if r.ok)
        out["gen_tokens_per_s"] = tokens / seconds
    else:
        lats = [r.latency for r in records for _ in range(mix.requests)]
        out["request_p95_s"] = traffic.nearest_rank(lats, 95)
    return out


def _start_trace(tmp: Path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)


def run(root: Path, args, t_proc: float, *, bench_dir: Path = spec.BENCH,
        devices=None, peaks=None) -> int:
    """One run of ``args.workload``.  ``devices`` and ``peaks`` skip the
    look for a chip; only the tests pass them."""
    cell = spec.load_cell(root, args.workload, bench_dir)
    devs = devices or device.require_tpu(cell.chips)
    import jax
    from repro.core import EngineManager, TelemetryConfig
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models import model as M
    from repro.models.common import ArchConfig
    sys.path.insert(0, str(cell.bench_dir))
    from deployments.lm_serve import Deployment
    from .drive import Driver

    cache = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = device.CompileClock()
    info = device.device_info(devs)
    peaks = peaks or device.peaks(info["kind"])
    arch = dict(cell.config["arch"])
    cfg = ArchConfig(**arch)
    mix = Mix.from_file(cell.traffic)
    seed = int(args.seed)
    key_int = traffic.weight_key_int(seed)
    log(f"cell {cell.name} seed {seed} on {info}; compile cache {cache}")

    params = jax.block_until_ready(
        M.init_params(cfg, jax.random.PRNGKey(key_int)))
    dep = Deployment(cfg, params, microbatch=mix.microbatch,
                     decode_tokens=mix.decode_tokens)
    graph = dep.graph(mix.requests)
    eng = mix.engine
    mgr = EngineManager(num_nodes=eng["num_nodes"],
                        workers_per_node=eng["workers_per_node"],
                        max_concurrent=eng["max_concurrent"],
                        max_pending=NO_LIMIT, keep_finished=NO_LIMIT,
                        telemetry=TelemetryConfig(timeline=bool(args.trace)))
    driver = Driver(mgr, graph, mix)
    for s in traffic.warmup_sessions(mix, seed, cfg.vocab_size):
        rec = driver.run_one(s)
        if not rec.ok:
            raise RuntimeError(f"warm-up session failed: {rec.error}")
    driver.records.clear()
    log(f"set-up done: {clock.count} compiles, {clock.total:.3f} s compiling")
    compiles0 = clock.count

    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-")) if args.trace else None
    stopper = None
    if tmp is not None:
        from .trace import MARK

        def mark(name):
            with jax.profiler.TraceAnnotation(MARK + name):
                pass

        def stop():
            mark("close")
            jax.profiler.stop_trace()
        _start_trace(tmp)
        mark("open")
        stopper = threading.Timer(min(TRACE_S, args.seconds), stop)
    t0 = time.monotonic()
    setup_s = t0 - t_proc
    if stopper is not None:
        stopper.start()
    lateness = 0.0
    if mix.loop == "closed":
        streams = [traffic.client_sessions(mix, seed, c, cfg.vocab_size)
                   for c in range(mix.clients)]
        driver.closed(streams, t0, args.seconds)
    else:
        sched = traffic.open_schedule(mix, seed, args.seconds, cfg.vocab_size)
        lateness = driver.open(sched, t0, mix.late_s)
    t_end = time.monotonic()
    records = list(driver.records)
    in_window = clock.count - compiles0
    e2e = end_to_end(mix, records, t0, args.seconds)
    peak = device.peak_bytes(devs)
    mgr.close()
    dep.free()
    if mix.loop == "open":
        by_due = [r.latency for r in sorted(records, key=lambda r: r.due)]
        q = max(1, len(by_due) // 4)
        lats = sorted(by_due)
        log(f"open loop: {len(records)} sessions due, "
            f"{sum(r.ok for r in records)} answered; session latency p50 "
            f"{traffic.nearest_rank(lats, 50):.4f} s, p95 "
            f"{traffic.nearest_rank(lats, 95):.4f} s, max {lats[-1]:.4f} s; "
            f"mean of the first quarter due {sum(by_due[:q]) / q:.4f} s, "
            f"of the last {sum(by_due[-q:]) / q:.4f} s")
    log("sessions (sent, answered, tokens; s from the window's opening): "
        + json.dumps([[round(r.due - t0, 4), round(r.done - t0, 4)
                       if r.done else None,
                       int(r.tokens.size) if r.ok else 0] for r in records]))
    log(f"window {args.seconds} s closed after {t_end - t0:.3f} s; "
        f"{len(records)} sessions; {in_window} compiles in the window; "
        f"sender late by at most {lateness:.4f} s")

    data = RunData(arch=arch, mix=mix, peaks=peaks, records=records)
    if stopper is not None:
        stopper.join()
        from . import trace as tr_mod
        t = time.monotonic()
        data.trace = tr_mod.load_xplane(next(tmp.rglob("*.xplane.pb")))
        shutil.rmtree(tmp, ignore_errors=True)
        win = tr_mod.window_ns(data.trace)
        data.window_s = (win[1] - win[0]) / 1e9 if win else 0.0
        data.busy_s = tr_mod.busy_s(data.trace)
        log(f"trace read in {time.monotonic() - t:.3f} s")

    # the comparison that decides `correct`
    attempted = len(records) * mix.requests
    failed = sum(mix.requests for r in records if not r.ok)
    ordered = sorted(records, key=lambda r: (r.session.client,
                                             r.session.index))
    served = [check.Served(r.session.prompts[i], r.tokens[i])
              for r in ordered if r.ok for i in range(mix.requests)]
    chosen = check.sample(served, mix.check_requests, seed)
    t = time.monotonic()
    readings = check.compare(cell.reference(), arch, key_int, chosen,
                             control=bool(args.control))
    log(f"reference over {len(chosen)} requests in "
        f"{time.monotonic() - t:.3f} s")
    compared = {name: {"value": readings[name], "limit": lim, "rule": "<="}
                for name, lim in check.limits(cell.config,
                                              cell.traffic_name).items()}
    compared["failed_requests"] = {"value": failed, "limit": 0, "rule": "<="}
    compared["served_tokens_checked"] = {
        "value": sum(len(s.tokens) for s in chosen),
        "limit": mix.check_requests * mix.decode_tokens, "rule": ">="}
    correct = all(c["value"] <= c["limit"] if c["rule"] == "<="
                  else c["value"] >= c["limit"] for c in compared.values())

    result: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                              "failed": failed}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"]).read(data)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": units[m["name"]]}
                   for m in cell.end_to_end if m["name"] in e2e}
    result["metrics"] = metrics
    result["device"] = dict(info, memory_peak_bytes=peak)
    if args.trace:
        from . import trace as tr_mod
        result["device"].update(busy_s=data.busy_s, window_s=data.window_s)
        result["breakdown"] = {"device_ops": tr_mod.top_ops(data.trace),
                               "idle_gaps": tr_mod.idle_gaps(data.trace)}
    if args.control:
        result["served"] = readings["served"]
        log(f"control in the program's place; served tokens read "
            f"{readings['served']!r}")
    result["check"] = compared
    for name, c in compared.items():
        log(f"check {name}={c['value']!r} must be {c['rule']} "
            f"{c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
