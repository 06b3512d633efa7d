"""Traffic for the benchmark: one general generator, driven by a data file.

A traffic mix is a JSON file under ``benchmarks/traffic/`` (lengths, loop kind,
callers or rate, sharing). This module turns it and ``--seed`` into requests,
drives ``POST /v1/chat/completions`` (SSE, greedy) over loopback and times
every burst on this process's clock. Standard library only: the process that
imports it never touches JAX.

The tokenizer the server is given (``launcher.py``) is the ``[id]`` vocabulary:
``<unk> <s> </s>``, the 256 byte-fallback pieces, then ``[id]`` for every other
id. A prompt of lower-case letters and spaces therefore encodes to one byte
token a character, and the response text spells the emitted ids. The copies of
the chat template and of the encoding below are the benchmark's own: the count
they give is checked in every run against the server's
``dllama_prompt_tokens_total``.

Every seed sends the same lengths in the same order: a block of ``block``
requests holds one prompt length and one output length from each of ``block``
strata of the two distributions, paired and ordered by the mix's own
``order_seed``, and ``--seed`` decides the characters (and, in an open loop,
nothing else: the arrivals are the mix's too). The seed changes what is said,
not how much or when: with the order drawn from the seed, three seeds of one
cell read 5,040, 5,589 and 5,852 ms at the 90th percentile of time to first
token (chip runs, PR 23), because a prompt waits behind every prefill piece
queued before it and the tail follows the places of the long prompts.

Arithmetic copied from ``scripts/workloads.py`` (first content delta, mean gap
between tokens, ``pct``); its scenarios are not used.
"""

from __future__ import annotations

import http.client
import json
import math
import queue
import random
import re
import threading
import time

BOS_ID = 1
ALPHABET = "abcdefghijklmnopqrstuvwxyz"
_ID_RE = re.compile(r"\[(\d+)\]")


def render_llama2_turn(user: str) -> str:
    """The server's rendering of a one-message chat (template ``llama2``)."""
    return f"[INST] {user} [/INST]"


#: tokens the template and BOS add to a prompt of n characters
TEMPLATE_OVERHEAD = 1 + len(render_llama2_turn(""))


def encode_prompt(user: str) -> list:
    """Token ids of the templated prompt under the ``[id]`` vocabulary: BOS,
    then one byte-fallback token (byte + 3) a character. Holds for text that
    never spells ``[digits]``, which is all this module generates."""
    return [BOS_ID] + [b + 3 for b in render_llama2_turn(user).encode()]


def served_ids(text: str) -> list | None:
    """The ids a response text spells, or None where it is not a run of
    ``[id]`` pieces (a byte token was emitted: the launcher's weights make
    that impossible, so it counts as a wrong answer)."""
    ids = [int(m) for m in _ID_RE.findall(text)]
    if "".join(f"[{i}]" for i in ids) != text:
        return None
    return ids


def pct(values: list, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order statistics."""
    if not values:
        raise ValueError("no samples")
    s = sorted(values)
    k = (len(s) - 1) * q
    lo, hi = math.floor(k), math.ceil(k)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


# ---------------------------------------------------------------------------
# lengths and requests from the data file and the seed
# ---------------------------------------------------------------------------

def _strata(dist: dict, n: int) -> list:
    """n values, the mid-quantiles of n equal strata of the distribution."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    lo, hi = float(dist["min"]), float(dist["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "loguniform":
        return [int(round(math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))))
                for q in qs]
    if kind == "uniform":
        return [int(round(lo + q * (hi - lo))) for q in qs]
    raise ValueError(f"unknown distribution {kind!r}")


class Request:
    __slots__ = ("index", "user", "prompt_tokens", "max_tokens", "due_s")

    def __init__(self, index: int, user: str, prompt_tokens: int,
                 max_tokens: int, due_s: float | None = None):
        self.index = index
        self.user = user
        self.prompt_tokens = prompt_tokens  # BOS and template included
        self.max_tokens = max_tokens
        self.due_s = due_s  # open loop: seconds after the loop's start


def _text(rng: random.Random, n_chars: int) -> str:
    """n_chars of letters with a space about every sixth place; no two
    prompts share a prefix beyond the template but by chance."""
    out = []
    for i in range(n_chars):
        out.append(" " if i % 6 == 5 else rng.choice(ALPHABET))
    return "".join(out)


def make_requests(mix: dict, seed: int, count: int) -> list:
    """``count`` requests of the mix: lengths and their order (and an open
    loop's arrivals) from the mix's ``order_seed``, characters from ``seed``.
    ``prompt_tokens`` in the file counts BOS and the template, as the server
    counts them."""
    rng = random.Random(int(mix.get("order_seed", 0)))
    text_rng = random.Random(seed)
    block = int(mix.get("block", 16))
    plens = _strata(mix["prompt_tokens"], block)
    olens = _strata(mix["max_tokens"], block)
    out: list = []
    while len(out) < count:
        p, o = list(plens), list(olens)
        rng.shuffle(p)
        rng.shuffle(o)
        for pl, ol in zip(p, o):
            n_chars = max(1, pl - TEMPLATE_OVERHEAD)
            out.append(Request(len(out), _text(text_rng, n_chars),
                               n_chars + TEMPLATE_OVERHEAD, max(1, ol)))
    out = out[:count]
    if mix["loop"] == "open":
        arr = mix["arrivals"]
        rate, t = float(arr["rate_per_s"]), 0.0
        cv = float(arr.get("cv", 1.0))  # 1 = Poisson; above 1 = gamma bursts
        shape = 1.0 / (cv * cv)
        for r in out:
            t += rng.gammavariate(shape, 1.0 / (rate * shape))
            r.due_s = t
    return out


def warm_requests(mix: dict) -> list:
    """The fixed warm set of the mix (not from the seed): a list of phases,
    each a list of Requests sent together. Text is fixed per place."""
    rng = random.Random(0x5EED)
    phases = []
    for phase in mix["warm"]:
        reqs = []
        for pl, ol in phase:
            n_chars = max(1, int(pl) - TEMPLATE_OVERHEAD)
            reqs.append(Request(-1, _text(rng, n_chars),
                                n_chars + TEMPLATE_OVERHEAD, int(ol)))
        phases.append(reqs)
    return phases


# ---------------------------------------------------------------------------
# one request over HTTP, timed on this clock
# ---------------------------------------------------------------------------

class Result:
    __slots__ = ("request", "sent", "first", "last", "bursts", "text",
                 "status", "error", "done", "finish")

    def __init__(self, request: Request):
        self.request = request
        self.sent = None  # monotonic seconds; open loop: when it was DUE
        self.first = None  # first content delta
        self.last = None  # last content delta
        self.bursts: list = []  # (monotonic seconds, tokens in the burst)
        self.text = ""
        self.status = None
        self.error = None
        self.done = False
        self.finish = None

    @property
    def ok(self) -> bool:
        return self.status == 200 and self.done and self.error is None

    def ids(self) -> list | None:
        return served_ids(self.text)


def do_request(port: int, rq: Request, sent_at: float | None = None,
               timeout: float = 300.0) -> Result:
    """Send one greedy streaming completion now and read it to the end.
    ``sent_at``: the time the request was due (open loop); latency counts
    from there."""
    res = Result(rq)
    body = json.dumps({
        "model": "bench", "temperature": 0.0, "max_tokens": rq.max_tokens,
        "stream": True,
        "messages": [{"role": "user", "content": rq.user}],
    }).encode()
    res.sent = time.monotonic() if sent_at is None else sent_at
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/chat/completions", body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        res.status = resp.status
        if resp.status != 200:
            res.error = resp.read()[:200].decode("utf-8", "replace")
            return res
        buf = b""
        pieces: list = []
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            now = time.monotonic()
            buf += chunk
            n_new = 0
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                for line in event.split(b"\n"):
                    if not line.startswith(b"data: "):
                        continue
                    payload = line[6:]
                    if payload == b"[DONE]":
                        res.done = True
                        continue
                    obj = json.loads(payload)
                    if "error" in obj:
                        res.error = str(obj["error"])[:200]
                    for ch in obj.get("choices", []):
                        piece = (ch.get("delta") or {}).get("content")
                        if piece:
                            pieces.append(piece)
                            n_new += piece.count("]")  # one ] an [id] piece
                        if ch.get("finish_reason"):
                            res.finish = ch["finish_reason"]
            if n_new:
                if res.first is None:
                    res.first = now
                res.last = now
                res.bursts.append((now, n_new))
            if res.done:
                break
        res.text = "".join(pieces)
        if not res.done and res.error is None:
            res.error = "stream ended without [DONE]"
        return res
    except (OSError, ValueError, http.client.HTTPException) as e:
        res.error = f"transport: {e!r}"
        return res
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# the loops
# ---------------------------------------------------------------------------

class Loop:
    """Drives the mix against the server from ``start()`` until ``stop()``.

    ``closed``: ``callers`` threads, each sending its next request when its
    reply ends. ``open``: every request on its own thread at its due time,
    timed from when it was due; how late the generator fired is kept.
    Results come back in completion order from ``results``."""

    def __init__(self, port: int, mix: dict, requests: list):
        self.port = port
        self.mix = mix
        self._todo: queue.Queue = queue.Queue()
        for r in requests:
            self._todo.put(r)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self.results: list = []
        self.inflight: dict = {}  # id(request) -> when it was sent (or due)
        self.lateness_s: list = []
        self._threads: list = []
        self.t_start = None

    def _caller(self) -> None:
        while not self._stop.is_set():
            try:
                rq = self._todo.get_nowait()
            except queue.Empty:
                return
            with self._lock:
                self.inflight[id(rq)] = time.monotonic()
            res = do_request(self.port, rq)
            with self._lock:
                del self.inflight[id(rq)]
                self.results.append(res)

    def _fire(self, rq: Request) -> None:
        due = self.t_start + rq.due_s
        delay = due - time.monotonic()
        if delay > 0 and self._stop.wait(delay):
            return
        with self._lock:
            self.lateness_s.append(max(0.0, time.monotonic() - due))
            self.inflight[id(rq)] = due
        res = do_request(self.port, rq, sent_at=due)
        with self._lock:
            del self.inflight[id(rq)]
            self.results.append(res)

    def _dispatch_open(self) -> None:
        while not self._stop.is_set():
            try:
                rq = self._todo.get_nowait()
            except queue.Empty:
                return
            # start each request's thread a little before it is due
            wait = self.t_start + rq.due_s - 0.05 - time.monotonic()
            if wait > 0 and self._stop.wait(wait):
                return
            t = threading.Thread(target=self._fire, args=(rq,), daemon=True)
            t.start()
            with self._lock:
                self._threads.append(t)

    def start(self) -> None:
        self.t_start = time.monotonic()
        if self.mix["loop"] == "closed":
            for _ in range(int(self.mix["callers"])):
                t = threading.Thread(target=self._caller, daemon=True)
                t.start()
                self._threads.append(t)
        elif self.mix["loop"] == "open":
            t = threading.Thread(target=self._dispatch_open, daemon=True)
            t.start()
            self._threads.append(t)
        else:
            raise ValueError(f"unknown loop kind {self.mix['loop']!r}")

    def snapshot(self) -> list:
        with self._lock:
            return list(self.results)

    def pending_before(self, t: float) -> int:
        """Requests sent (or due) before ``t`` that have not ended."""
        with self._lock:
            return sum(1 for sent in self.inflight.values() if sent < t)

    def stop(self, timeout: float = 120.0) -> bool:
        """No new request; wait for those in flight. True when all ended."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                threads = list(self._threads)
            alive = [t for t in threads if t.is_alive()]
            if not alive:
                return True
            if time.monotonic() > deadline:
                return False
            alive[0].join(timeout=0.2)


def run_together(port: int, reqs: list, timeout: float = 900.0) -> list:
    """Send a warm phase's requests at once; results in the phase's order."""
    out: list = [None] * len(reqs)

    def one(i: int) -> None:
        out[i] = do_request(port, reqs[i], timeout=timeout)

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a warm request did not end")
    return out


# ---------------------------------------------------------------------------
# metrics of a window, from the results
# ---------------------------------------------------------------------------

def window_stats(results: list, t0: float, t1: float) -> dict:
    """The client's numbers for the window [t0, t1): requests sent in it
    (all of them: a failed one has no latency and counts as failed), output
    tokens received in it, prompt tokens of the requests whose first token
    arrived in it."""
    sent = [r for r in results if t0 <= r.sent < t1]
    okay = [r for r in sent if r.ok and r.first is not None]
    ttft = [(r.first - r.sent) * 1000.0 for r in okay]
    tpot = []
    for r in okay:
        n = sum(k for _, k in r.bursts)
        if n > 1 and r.last > r.first:
            tpot.append((r.last - r.first) * 1000.0 / (n - 1))
    out_tokens = sum(k for r in results for t, k in r.bursts if t0 <= t < t1)
    prompt_tokens = sum(r.request.prompt_tokens for r in results
                        if r.first is not None and t0 <= r.first < t1)
    return {
        "attempted": len(sent),
        "failed": len(sent) - len(okay),
        "ttft_ms": ttft,
        "tpot_ms": tpot,
        "out_tokens": out_tokens,
        "prompt_tokens": prompt_tokens,
        "seconds": t1 - t0,
        "finished": okay,
    }
