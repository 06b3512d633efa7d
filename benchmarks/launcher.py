"""The child that holds the chip: the server as ``cli serve`` builds it, on
weights made from ``--seed``, with the profiler and the reference beside it.

Started by ``run.py`` (which never imports JAX). It builds what
``api_server.serve()`` builds, ``configure_compile_cache()`` first, then
``create_server(ServerState(engine, tok, cfg, ...))``, with one substitution:
where ``cli.load_engine`` reads a weight file, the planes come from the
configuration's family (``families.load``: one jitted init program, already
in the layout the program serves) and go to ``Engine(cfg, params,
SamplerConfig(temperature=0), cache_dtype=bfloat16, mesh=None)`` with
``cfg.dtype = "bfloat16"``: the TPU defaults of ``load_engine``. The family
is all this file knows of the model: its configuration, weights and
reference. The tokenizer is the ``[id]`` vocabulary at the program's
vocabulary size, built in memory.

It talks to its parent in lines: it reads one JSON command a line on stdin
and answers with one line ``@@ {json}`` on stdout (everything else the program
prints goes to stderr). Commands: ``trace_start``, ``trace_stop``, ``finish`` (stops the server, reads the peak
memory, frees the program's state, reduces the trace if one was taken, runs
the reference over the sampled requests, answers and exits).

No chip, fewer chips than the cell asks for, or a ``device_kind`` that is not
in ``peaks.json``: exit code 3, nothing answered. ``--rehearse`` (the harness's
own tests) lets it run on the CPU at a tiny size; what it answers there names
the CPU and is never a device metric.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(obj: dict) -> None:
    sys.__stdout__.write("@@ " + json.dumps(obj) + "\n")
    sys.__stdout__.flush()


def id_tokenizer(vocab_size: int):
    """``<unk> <s> </s>``, the 256 byte-fallback pieces, then ``[id]`` for
    every other id: the response text spells the emitted ids."""
    from dllama_tpu.formats.tokenizer_file import TokenizerData
    from dllama_tpu.tokenizer.bpe import Tokenizer

    vocab = [b"<unk>", b"<s>", b"</s>"] + [b"<0x%02X>" % b for b in range(256)]
    vocab += [b"[%d]" % i for i in range(len(vocab), vocab_size)]
    return Tokenizer(TokenizerData(vocab=vocab[:vocab_size],
                                   scores=[0.0] * vocab_size,
                                   bos_id=1, eos_id=2))


def plant_fault(kind: str) -> None:
    """Break the timed path underneath (the harness's own tests, and the
    builder's readings of what a fault reads at a cell's size): ``token``
    alters the first token of every chunk the pool's decode step hands out
    (one token in ``batch_chunk``); ``token1`` alters only the first token a
    request is handed, the one after the prefill-to-decode hand-off."""
    if kind not in ("token", "token1"):
        raise SystemExit(f"unknown fault {kind!r}")
    from dllama_tpu.runtime import generate

    real_step = generate.BatchSession.step_chunk
    real_begin = generate.BatchSession.admit_begin
    fresh_rows: set = set()

    def begin(self, *a, **kw):
        handle = real_begin(self, *a, **kw)
        fresh_rows.add(handle)
        return handle

    def altered(self):
        fresh = real_step(self)
        for handle, burst in fresh.items():
            if burst and (kind == "token" or handle in fresh_rows):
                n = self.eng.cfg.vocab_size - 259  # another [id] piece
                burst[0] = 259 + (burst[0] - 259 + 7919) % n
            if burst:
                fresh_rows.discard(handle)
        return fresh

    generate.BatchSession.admit_begin = begin
    generate.BatchSession.step_chunk = altered


def peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    sys.stdout = sys.stderr  # the program's own prints; answers go via say()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    with open(args.config) as f:
        conf = json.load(f)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]
    server = conf["server"]
    n_tp = int(conf.get("tp", 1))

    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not args.rehearse:
        if dev["platform"] != "tpu" or dev["count"] < args.chips \
                or dev["kind"] not in peaks:
            print(f"launcher: needs {args.chips} TPU chip(s) of a kind in "
                  f"peaks.json, found {dev}", file=sys.stderr)
            return 3
    if args.fault:
        plant_fault(args.fault)

    from dllama_tpu.runtime.device import configure_compile_cache
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig
    from dllama_tpu.serving.api_server import (ServerState, create_server,
                                               drain_and_shutdown)

    import families
    import trace_reduce

    family = families.load(conf)
    cache_dir = configure_compile_cache()
    t_jax = time.monotonic()
    cfg = family.model_config(conf, server)
    if n_tp > 1:
        params, mesh = family.make_sharded_params(conf, cfg, n_tp, args.seed)
        planes = None  # the reference gets them after the window
    else:
        planes = family.make_planes(conf, args.seed)
        params, mesh = family.wrap_planes(planes, conf), None
    jax.block_until_ready(params)
    t_planes = time.monotonic()
    engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=0),
                    cache_dtype=jnp.dtype(server.get("cache_dtype", "bfloat16")),
                    mesh=mesh)
    if n_tp == 1:
        del params
    tok = id_tokenizer(cfg.vocab_size)
    state = ServerState(
        engine, tok, cfg, model_name=conf["name"],
        template=server.get("chat_template", "llama2"),
        default_sampler=SamplerConfig(temperature=0.0), default_seed=0,
        session_cache=int(server["session_cache"]),
        batch_window_ms=float(server["batch_window_ms"]),
        batch_max=int(server["batch_max"]),
        batch_chunk=int(server["batch_chunk"]),
        prefill_chunk=int(server.get("prefill_chunk", -1)),
        kv_buckets=int(server.get("kv_buckets", 1)),
        kv_bucket_min=int(server.get("kv_bucket_min", 0)),
        kv_pages=int(server.get("kv_pages", 0)))
    srv = create_server(state, host="127.0.0.1", port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    say({"ready": True, "port": srv.server_address[1], "device": dev,
         "compile_cache_dir": cache_dir,
         "seconds": {"jax_start": t_jax - t0, "planes": t_planes - t_jax,
                     "engine_and_server": time.monotonic() - t_planes}})

    tracing, traced = False, False
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        if cmd["cmd"] == "trace_start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(args.trace_dir, profiler_options=opts)
            tracing = True
            say({"tracing": True})
        elif cmd["cmd"] == "trace_stop":
            jax.profiler.stop_trace()  # reduced at "finish", off the window
            tracing, traced = False, True
            say({"tracing": False})
        elif cmd["cmd"] == "finish":
            if tracing:
                jax.profiler.stop_trace()
            drained = drain_and_shutdown(state, srv, 30.0)
            srv.server_close()
            thread.join(timeout=30)
            peak = peak_bytes()
            # free the program's state before the reference runs
            state._sessions.clear()
            state.batcher = None
            del engine, state, srv
            gc.collect()
            out = {}
            if traced:
                t1 = time.monotonic()
                record = trace_reduce.load_xplane(args.trace_dir)
                out["trace"] = trace_reduce.reduce(record)
                del record
                out["reduce_seconds"] = time.monotonic() - t1
            t1 = time.monotonic()
            res = {}
            if planes is None:
                planes = family.planes_of(params, conf)
                del params
            if cmd.get("samples"):
                res = family.compare(planes, conf, cmd["samples"],
                                     cmd.get("stand_ins", ()))
            out.update({"finished": True, "drained": drained,
                        "memory_peak_bytes": peak, "compare": res,
                        "reference_seconds": time.monotonic() - t1})
            say(out)
            return 0
        else:
            say({"error": f"unknown command {cmd['cmd']!r}"})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
