"""The LM serving deployment the benchmark drives through the engine.

A copy of ``launch/serve.py``'s non-streaming serving graph: the session's
``reqs`` input is scattered into microbatches, each microbatch runs
prefill -> ``kv`` -> decode -> ``gen``, and a gather assembles
``responses``.  The apps call only the program's
``launch/serve.py:prefill_microbatch`` and ``serving_steps(cfg)``, with the
same greedy decode loop as ``serve.py``.

What differs from ``serve.py`` is where the prompts come from: each
session's prompts are its ``reqs`` input, an int32 array of shape
(requests, prompt_len), so every session can carry its own prompts and
length.  Each app call is wrapped in a ``jax.profiler.TraceAnnotation``
(``bench.prefill``, ``bench.decode``, ``bench.assemble``) whose arguments
say what it served, so the trace reduction can attribute device work and
idle gaps to it.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import register_app
from repro.dsl import GraphBuilder
from repro.launch.serve import prefill_microbatch, serving_steps

PREFILL_APP = "bench/lm_serve/prefill"
DECODE_APP = "bench/lm_serve/decode"
ASSEMBLE_APP = "bench/lm_serve/assemble"


class Deployment:
    """One model served with a fixed microbatch and decode length.

    ``params`` stays on the device for the deployment's life; ``free``
    deletes it so that the correctness reference can have the chip."""

    def __init__(self, cfg: Any, params: Any, *, microbatch: int,
                 decode_tokens: int) -> None:
        self.cfg = cfg
        self.params = params
        self.microbatch = microbatch
        self.decode_tokens = decode_tokens
        # the registry marks the function it is given, so give it plain
        # functions rather than bound methods
        register_app(PREFILL_APP, device=True)(
            lambda inputs, outputs, app: self._prefill(inputs, outputs, app))
        register_app(DECODE_APP, device=True)(
            lambda inputs, outputs, app: self._decode(inputs, outputs, app))
        register_app(ASSEMBLE_APP)(
            lambda inputs, outputs, app: self._assemble(inputs, outputs, app))

    def graph(self, requests: int):
        """The serving graph for sessions of ``requests`` prompts."""
        assert requests % self.microbatch == 0, (requests, self.microbatch)
        n_micro = requests // self.microbatch
        g = GraphBuilder("bench-lm-serve")
        g.data("reqs")
        with g.scatter("mb", n_micro):
            g.component("prefill", app=PREFILL_APP, time=0.5)
            g.data("kv", volume=1e6)
            g.component("decode", app=DECODE_APP, time=1.0)
            g.data("gen")
        with g.gather("all", n_micro):
            g.component("assemble", app=ASSEMBLE_APP, time=0.01)
        g.data("responses")
        g.chain("reqs", "prefill", "kv", "decode", "gen")
        g.connect("gen", "assemble")
        g.chain("assemble", "responses")
        return g.graph()

    def _prefill(self, inputs, outputs, app) -> None:
        (mb,) = app.meta["oid"]
        prompts = inputs[0].read()
        chunk = prompts[mb * self.microbatch:(mb + 1) * self.microbatch]
        prompt_len = chunk.shape[1]
        with jax.profiler.TraceAnnotation("bench.prefill", mb=chunk.shape[0],
                                          prompt_len=prompt_len):
            next_tok, cache = prefill_microbatch(
                self.cfg, self.params, chunk,
                prompt_len + self.decode_tokens)
        for o in outputs:
            o.write({"next": next_tok, "cache": cache,
                     "prompt_len": prompt_len})

    def _decode(self, inputs, outputs, app) -> None:
        _, decode_one = serving_steps(self.cfg)
        st = inputs[0].read()
        tok, cache, prompt_len = st["next"], st["cache"], st["prompt_len"]
        with jax.profiler.TraceAnnotation("bench.decode", mb=tok.shape[0],
                                          prompt_len=prompt_len):
            toks = [tok]
            for i in range(self.decode_tokens - 1):
                tok, cache = decode_one(self.params, cache, tok,
                                        jnp.int32(prompt_len + i))
                toks.append(tok)
            out = np.asarray(jnp.concatenate(toks, axis=1))
        for o in outputs:
            o.write(out)

    def _assemble(self, inputs, outputs, app) -> None:
        with jax.profiler.TraceAnnotation("bench.assemble"):
            out = np.concatenate([i.read() for i in inputs], axis=0)
        for o in outputs:
            o.write(out)

    def free(self) -> None:
        """Delete the weights from the device."""
        for leaf in jax.tree.leaves(self.params):
            leaf.delete()
        self.params = None
