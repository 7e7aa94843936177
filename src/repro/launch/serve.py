"""Batched serving driver through the graph engine (MUSER analogue, §6).

Requests stream in like MUSER's correlator frames: the logical graph
Scatters a request batch into micro-batches, each micro-batch flows through
prefill -> decode Drops, and a Gather assembles responses.  InMemory Drops
carry the KV caches between prefill and decode exactly like MUSER's
visibility frames ("data of these types needs high I/O bandwidth").

With ``--sessions N`` the same graph shape is served N times through a
resident :class:`~repro.core.manager.EngineManager`: the first session
pays translate+map, every later one is a template-cache hit that only
materializes fresh session state — the paper's "translate once, run
per-observation" manager shape, reported as sessions/s with p50/p99
session latency.

CLI:
  PYTHONPATH=src python -m repro.launch.serve --requests 8 --decode 16
  PYTHONPATH=src python -m repro.launch.serve --sessions 8 --concurrent 4
  PYTHONPATH=src python -m repro.launch.serve --sessions 8 \
      --stats-json results/serve_stats.json   # registry snapshot dump
  PYTHONPATH=src python -m repro.launch.serve --arch granite_moe_3b_a800m \
      --full --execution compiled --prompt 512 --decode 32   # published width
"""
from __future__ import annotations

import argparse
import functools
import json
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_NAMES, get_config, get_smoke_config
from ..core import (EngineConfig, EngineManager, Pipeline, TelemetryConfig,
                    register_app)
from ..dsl import GraphBuilder
from ..models import model as M
from ..models.common import ArchConfig
from ..train import make_decode_step, make_prefill_step
from .compile_cache import setup_compile_cache


@functools.lru_cache(maxsize=None)
def serving_steps(cfg: ArchConfig) -> Tuple[Callable, Callable]:
    """The jitted prefill and greedy decode steps of ``cfg``: one pair per
    config, shared by the served apps and :func:`reference_tokens`, so both
    run the same compiled programs."""
    return jax.jit(make_prefill_step(cfg)), jax.jit(make_decode_step(cfg))


def make_prompts(cfg: ArchConfig, num_requests: int,
                 prompt_len: int) -> np.ndarray:
    """The fixed prompt batch every serving run uses (seeded)."""
    rng = np.random.default_rng(0)
    return rng.integers(0, cfg.vocab_size,
                        size=(num_requests, prompt_len)).astype(np.int32)


def prefill_microbatch(cfg: ArchConfig, params: Any, chunk: np.ndarray,
                       max_seq: int) -> Tuple[jax.Array, Dict[str, Any]]:
    """Prefill one microbatch of prompts; returns the first generated
    token per row, (mb, 1), and the cache grown to ``max_seq``."""
    prefill_step, _ = serving_steps(cfg)
    mb, prompt_len = chunk.shape
    batch = {"tokens": jnp.asarray(chunk)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros(
            (mb, max(prompt_len // cfg.encoder_ratio, 1), cfg.d_model),
            jnp.float32)
    next_tok, cache = prefill_step(params, batch)
    # grow cache to max_seq for the decode phase
    grown = M.init_cache(cfg, mb, max_seq)

    def fill(dst, src):
        pad = [(0, d - s) for d, s in zip(dst.shape, src.shape)]
        return jnp.pad(src, pad).astype(dst.dtype)
    return next_tok[:, None], jax.tree.map(fill, grown, cache)


def _record_moe_counts(metrics: Any, counts: Dict[str, Any]) -> None:
    """Add one decode app's MoE routing counters (its cache's ``moe``
    entry, ``models/model.py:init_moe_counts``) to ``metrics``; one read
    from the device."""
    c = jax.device_get(counts)
    metrics.counter("moe.rows_routed").inc(int(c["rows"].sum()))
    metrics.counter("moe.layer_steps").inc(int(c["steps"].sum()))
    metrics.counter("moe.experts_touched").inc(int(c["touched"].sum()))
    metrics.gauge("moe.expert_rows_max").set_max(int(c["rows_max"].max()))


def reference_tokens(cfg: ArchConfig, params: Any, prompts: np.ndarray, *,
                     microbatch: int, decode_steps: int) -> np.ndarray:
    """The plain reference of the serving graph: the same prefill and
    decode steps run straight, one microbatch after another, with no
    engine.  Returns the (num_requests, decode_steps) greedy tokens the
    served ``responses`` must equal."""
    _, decode_one = serving_steps(cfg)
    prompt_len = prompts.shape[1]
    rows = []
    for start in range(0, len(prompts), microbatch):
        tok, cache = prefill_microbatch(
            cfg, params, prompts[start:start + microbatch],
            prompt_len + decode_steps)
        toks = [tok]
        for i in range(decode_steps - 1):
            tok, cache = decode_one(params, cache, tok,
                                    jnp.int32(prompt_len + i))
            toks.append(tok)
        rows.append(np.asarray(jnp.concatenate(toks, axis=1)))
    return np.concatenate(rows, axis=0)


def _dump_stats(path: str, payload: Dict[str, Any]) -> None:
    """Write the observability dump (--stats-json): the MetricsRegistry
    snapshot plus whatever serving stats the caller collected."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as fh:
        json.dump(payload, fh, indent=2, default=repr)
    print(f"[serve] stats written to {p}")


def run_serving(cfg: ArchConfig, *, num_requests: int = 8,
                microbatch: int = 4, prompt_len: int = 32,
                decode_steps: int = 16, num_nodes: int = 2,
                sessions: int = 1, max_concurrent: int = 4,
                stats_json: Optional[str] = None,
                streaming: bool = False, execution: str = "objects",
                hooks: Any = None, params: Any = None) -> Dict[str, Any]:
    """Serve ``num_requests`` prompts through the graph engine.

    ``streaming=True`` switches token delivery to the chunk lane: each
    decode step writes one ``(microbatch, step, tokens)`` chunk onto the
    ``gen`` drop, whose edge into the assembler is streaming — the
    assembler accumulates chunks as they arrive (on either engine) and
    concatenates at batch resolution.  ``hooks`` (ExecHooks) forwards to
    :meth:`Pipeline.execute` for chunk/wave observability.  ``params``
    defaults to ``init_params(cfg, PRNGKey(0))``; pass them to serve
    several runs from one copy.  The result's ``responses`` are the
    (num_requests, decode_steps) tokens of the (last) session.
    """
    assert num_requests % microbatch == 0
    n_micro = num_requests // microbatch
    max_seq = prompt_len + decode_steps

    if params is None:
        params = M.init_params(cfg, jax.random.PRNGKey(0))
    _, decode_one = serving_steps(cfg)
    prompts = make_prompts(cfg, num_requests, prompt_len)
    # the engine's metrics registry, set where the engine is made (None
    # with metrics off); an MoE model's decode apps add their routing
    # counters to it
    registry = None

    def count_moe(cache):
        if registry is not None and "moe" in cache:
            _record_moe_counts(registry, cache["moe"])

    @register_app("serve/prefill", device=True)
    def prefill_app(inputs, outputs, app):
        (mb,) = app.meta["oid"]
        next_tok, cache = prefill_microbatch(
            cfg, params, prompts[mb * microbatch:(mb + 1) * microbatch],
            max_seq)
        for o in outputs:
            o.write({"next": next_tok, "cache": cache})

    @register_app("serve/decode", device=True)
    def decode_app(inputs, outputs, app):
        st = inputs[0].read()
        tok, cache = st["next"], st["cache"]
        toks = [tok]
        for i in range(decode_steps - 1):
            tok, cache = decode_one(params, cache, tok,
                                    jnp.int32(prompt_len + i))
            toks.append(tok)
        count_moe(cache)
        for o in outputs:
            o.write(np.asarray(jnp.concatenate(toks, axis=1)))

    @register_app("serve/decode-stream", device=True)
    def decode_stream_app(inputs, outputs, app):
        # streaming variant: one chunk per generated token position so
        # the assembler overlaps with generation; chunks are tagged with
        # (microbatch id, step) — assembly order is interleave-proof
        (mb,) = app.meta["oid"]
        st = inputs[0].read()
        tok, cache = st["next"], st["cache"]
        for o in outputs:
            o.write((mb, 0, np.asarray(tok)))
        for i in range(decode_steps - 1):
            tok, cache = decode_one(params, cache, tok,
                                    jnp.int32(prompt_len + i))
            for o in outputs:
                o.write((mb, i + 1, np.asarray(tok)))
        count_moe(cache)

    @register_app("serve/assemble")
    def assemble(inputs, outputs, app):
        chunks = [i.read() for i in inputs]
        for o in outputs:
            o.write(np.concatenate(chunks, axis=0))

    def _assemble_finish(inputs, outputs, app):
        per_mb = app.scratch
        mbs = sorted(per_mb)
        rows = [np.concatenate([per_mb[m][s] for s in sorted(per_mb[m])],
                               axis=1) for m in mbs]
        for o in outputs:
            o.write(np.concatenate(rows, axis=0))

    @register_app("serve/assemble-stream", streaming=True,
                  finish=_assemble_finish)
    def assemble_stream(value, app):
        mb, step, tok = value
        app.scratch.setdefault(mb, {})[step] = tok

    g = GraphBuilder("serve")
    g.data("reqs")
    decode_kind = "serve/decode-stream" if streaming else "serve/decode"
    asm_kind = "serve/assemble-stream" if streaming else "serve/assemble"
    with g.scatter("mb", n_micro):
        g.component("prefill", app="serve/prefill", time=0.5)
        g.data("kv", volume=1e6)
        g.component("decode", app=decode_kind, time=1.0)
        g.data("gen")
    with g.gather("all", n_micro):
        g.component("assemble", app=asm_kind, time=0.01)
    g.data("responses")
    g.chain("reqs", "prefill", "kv", "decode", "gen")
    # token delivery: streaming mode rides the chunk lane gen -> assemble
    g.connect("gen", "assemble", streaming=streaming)
    g.chain("assemble", "responses")

    telemetry = TelemetryConfig(metrics=True) if stats_json else None
    if sessions > 1:
        with EngineManager(num_nodes=num_nodes, workers_per_node=2,
                           max_concurrent=max_concurrent,
                           max_pending=sessions,
                           telemetry=telemetry) as mgr:
            registry = mgr.metrics
            return _run_sessions(mgr, g.graph(), sessions=sessions,
                                 num_requests=num_requests,
                                 decode_steps=decode_steps,
                                 stats_json=stats_json)

    engine_cfg = EngineConfig(num_nodes=num_nodes, workers_per_node=2,
                              execution=execution, telemetry=telemetry)
    with Pipeline(engine_cfg) as p:
        registry = p.metrics
        p.translate(g.graph())
        p.deploy()
        t0 = time.monotonic()
        rep = p.execute(inputs={"reqs": num_requests}, timeout=3600,
                        hooks=hooks)
        wall = time.monotonic() - t0
        assert rep.ok, rep.errors[:3]
        out = (p.session.read("responses") if execution == "compiled"
               else p.session.drops["responses"].read())
        if stats_json:
            _dump_stats(stats_json, {
                "metrics": p.metrics.snapshot() if p.metrics else {},
                "spans": [{"name": s.name, "seconds": s.duration}
                          for s in p.spans],
                "wall_s": wall,
            })
    gen_tokens = num_requests * decode_steps
    result = {
        "responses": out,
        "responses_shape": tuple(out.shape),
        "wall_s": wall,
        "gen_tokens_per_s": gen_tokens / wall,
        "drops": sum(rep.status_counts.values()),
    }
    print(f"[serve] {cfg.name}: {num_requests} requests x {decode_steps} "
          f"tokens in "
          f"{wall:.2f}s ({result['gen_tokens_per_s']:.1f} tok/s), "
          f"responses {out.shape}")
    return result


def _run_sessions(mgr: EngineManager, lg, *, sessions: int,
                  num_requests: int, decode_steps: int,
                  stats_json: Optional[str] = None) -> Dict[str, Any]:
    """Serve one graph shape ``sessions`` times through the resident
    ``mgr``: one cold translate+map, then cache-hit sessions that share
    node pools and run up to its ``max_concurrent`` at once."""
    t0 = time.monotonic()
    tickets = [mgr.submit(lg, inputs={"reqs": num_requests},
                          timeout=3600, block=True)
               for _ in range(sessions)]
    reports = [t.result() for t in tickets]
    wall = time.monotonic() - t0
    for rep in reports:
        assert rep.ok, rep.errors[:3]
    out = tickets[-1].session.read("responses")
    lats = sorted(t.latency for t in tickets)
    stats = mgr.stats()
    if stats_json:
        _dump_stats(stats_json, stats)
    gen_tokens = sessions * num_requests * decode_steps
    result = {
        "responses": out,
        "responses_shape": tuple(out.shape),
        "sessions": sessions,
        "wall_s": wall,
        "sessions_per_s": sessions / wall,
        "gen_tokens_per_s": gen_tokens / wall,
        "p50_session_s": lats[len(lats) // 2],
        "p99_session_s": lats[min(len(lats) - 1,
                                  int(0.99 * (len(lats) - 1)))],
        "template_hits": stats["templates"]["hits"],
        "drops": sum(reports[0].status_counts.values()),
    }
    print(f"[serve] {sessions} sessions x {num_requests} requests in "
          f"{wall:.2f}s ({result['sessions_per_s']:.2f} sessions/s, "
          f"{result['gen_tokens_per_s']:.1f} tok/s, "
          f"p50 {result['p50_session_s']:.3f}s / "
          f"p99 {result['p99_session_s']:.3f}s, "
          f"{result['template_hits']} cache hits)")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--sessions", type=int, default=1,
                    help="serve the shape N times via a resident "
                         "EngineManager (template-cache hits after the "
                         "first)")
    ap.add_argument("--concurrent", type=int, default=4,
                    help="max concurrent sessions when --sessions > 1")
    ap.add_argument("--stats-json", type=str, default=None,
                    help="enable the metrics registry and dump its "
                         "snapshot (plus serving stats) to this path")
    ap.add_argument("--streaming", action="store_true",
                    help="stream decode tokens chunk-by-chunk into the "
                         "assembler (docs/streaming.md)")
    ap.add_argument("--execution", choices=("objects", "compiled"),
                    default="objects",
                    help="execution substrate for the single-session "
                         "path (--sessions 1)")
    ap.add_argument("--arch", choices=ARCH_NAMES, default="codeqwen15_7b",
                    help="registry config to serve")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config instead of its "
                         "smoke-size variant (needs the chip)")
    args = ap.parse_args()
    setup_compile_cache()
    cfg = (get_config if args.full else get_smoke_config)(args.arch)
    run_serving(cfg, num_requests=args.requests,
                microbatch=args.microbatch, prompt_len=args.prompt,
                decode_steps=args.decode, sessions=args.sessions,
                max_concurrent=args.concurrent,
                stats_json=args.stats_json, streaming=args.streaming,
                execution=args.execution)


if __name__ == "__main__":
    main()
