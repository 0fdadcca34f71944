"""The one traffic generator: a traffic file's parameters plus a seed give
the requests of a run, bit for bit (stdlib Mersenne Twister, stable across
platforms by contract).

Rewritten from ``tools/loadgen.py``'s ``LoadSpec``/``generate_trace`` (the
arrival processes, the weighted length mixes and the shared-prefix mix are
that file's; see PERF.md, open questions, for the original). What differs:
prompts are TEXT for the synthetic tokenizer (one token a character, plus
BOS and the leading space, so a prompt of ``n`` tokens is ``n - 2``
characters), the schedule is cut by time and not by count, and a closed
loop gets one request list per client.

A traffic file (``traffic/<mix>.json``):

  loop            "open" | "closed" | "replay"
  arrival         open loop: {"process": "poisson", "rate_per_s": r} (exactly
                  round(r * seconds) arrivals at uniform times) or
                  {"process": "mmpp", "rate_per_s": r, "burst_rate_x": x,
                   "p_enter": a, "p_exit": b}
  clients         closed loop: how many clients, each sending its next
                  request when its last completes
  prompt_tokens   {"<tokens>": weight, ...}   tokens count BOS and the space
  output_tokens   {"<tokens>": weight, ...}   sampled tokens per request;
                  both are dealt from shuffled decks in exact proportion
  shared_prefix   optional {"share": s, "prefixes": k, "prefix_tokens": n}
  who             who sends such traffic (prose, for PERF.md)
"""

from __future__ import annotations

import math
import random
from fractions import Fraction

# printable ASCII the synthetic tokenizer holds as single-character pieces;
# no space, so a prompt never depends on how runs of spaces encode
CHARS = "".join(chr(c) for c in range(33, 127))
PROMPT_OVERHEAD = 2   # BOS + the dummy-prefix space
MIN_PROMPT_TOKENS = 4


class _Deck:
    """Draws values in the mix's EXACT proportions: a deck holds each value
    as often as its weight says (the smallest deck that does), is shuffled
    by the seed and dealt out, then shuffled anew. Independent draws would
    make the amount of work in a window vary from seed to seed by more than
    any bound could allow (the saturated cell's tokens/s spread by 2.4 %
    with them; my chip run, PR 22); with a deck the seed sets the order and
    hardly the amount."""

    def __init__(self, table: dict, what: str):
        if not table:
            raise ValueError(f"traffic: {what} is empty")
        weights = [float(w) for w in table.values()]
        if min(weights) <= 0:
            raise ValueError(f"traffic: {what} weights must be positive")
        shares = [Fraction(w / sum(weights)).limit_denominator(100)
                  for w in weights]
        size = math.lcm(*(f.denominator for f in shares))
        self.values = [int(k) for k in table]
        self.cards = [v for v, f in zip(self.values, shares)
                      for _ in range(int(f * size))]
        self.hand: list = []

    def draw(self, rng: random.Random) -> int:
        if not self.hand:
            self.hand = list(self.cards)
            rng.shuffle(self.hand)
        return self.hand.pop()


def _text(rng: random.Random, n_chars: int) -> str:
    return "".join(rng.choice(CHARS) for _ in range(n_chars))


class _Shapes:
    """Draws one request's prompt and output length from the mixes."""

    def __init__(self, traffic: dict, seed: int):
        self.prompts = _Deck(traffic["prompt_tokens"], "prompt_tokens")
        self.outputs = _Deck(traffic["output_tokens"], "output_tokens")
        if min(self.prompts.values) < MIN_PROMPT_TOKENS:
            raise ValueError(f"traffic: a prompt has at least "
                             f"{MIN_PROMPT_TOKENS} tokens")
        sp = traffic.get("shared_prefix") or {}
        self.share = float(sp.get("share", 0.0))
        self.prefix_tokens = int(sp.get("prefix_tokens", 0))
        # the shared system prompts ride a DERIVED stream, so changing the
        # share does not reshuffle every other draw
        prefix_rng = random.Random(seed ^ 0x5EED)
        self.prefixes = [_text(prefix_rng, self.prefix_tokens)
                         for _ in range(int(sp.get("prefixes", 0)))]

    def draw(self, rng: random.Random) -> dict:
        n_prompt = self.prompts.draw(rng)
        n_out = self.outputs.draw(rng)
        head = ""
        if self.prefixes and rng.random() < self.share:
            head = self.prefixes[rng.randrange(len(self.prefixes))]
        n_chars = max(n_prompt - PROMPT_OVERHEAD, len(head) + 1)
        prompt = head + _text(rng, n_chars - len(head))
        return {"prompt": prompt,
                "prompt_tokens": len(prompt) + PROMPT_OVERHEAD,
                "output_tokens": n_out}


def _arrivals(arrival: dict, rng: random.Random, seconds: float) -> list:
    """Due times inside ``[0, seconds)``, sorted.

    ``poisson``: a Poisson process CONDITIONED ON ITS COUNT: exactly
    ``round(rate * seconds)`` arrivals at independent uniform times, which
    is what a Poisson process looks like given how many arrivals it had.
    Unconditioned, a 40 s window at 1.2 a second holds 48 +- 7 requests, 41
    to 61 from seed to seed: the offered load then swings from two thirds
    of the knee to past it, and median TTFT with it (186 to 372 ms, my chip
    run, PR 22). The seed sets when requests come, not how many.

    ``mmpp``: a two-state Markov-modulated Poisson process (calm at
    ``rate``, bursts at ``rate * burst_rate_x``, switching per arrival),
    generated forward and cut at the window's end."""
    rate = float(arrival["rate_per_s"])
    if rate <= 0:
        raise ValueError("traffic: rate_per_s must be positive")
    process = arrival.get("process", "poisson")
    if process == "poisson":
        return sorted(rng.uniform(0.0, seconds)
                      for _ in range(round(rate * seconds)))
    if process != "mmpp":
        raise ValueError(f"traffic: arrival process {process!r}")
    out, t, burst = [], 0.0, False
    while True:
        burst = (rng.random() >= float(arrival["p_exit"]) if burst
                 else rng.random() < float(arrival["p_enter"]))
        t += rng.expovariate(rate * (float(arrival["burst_rate_x"])
                                     if burst else 1.0))
        if t >= seconds:
            return out
        out.append(t)


def generate(traffic: dict, seed: int, seconds: float) -> dict:
    """``{"loop", "clients": [[request, ...], ...]}``. A request is
    ``{"id", "due_s", "prompt", "prompt_tokens", "output_tokens"}``. An open
    loop has one client whose requests carry their due times inside
    ``[0, seconds)``; a closed loop has one list per client, each long
    enough to outlast the window (``due_s`` is None: a request is due when
    the one before it completes); ``replay`` is a closed loop of one."""
    loop = traffic.get("loop")
    if loop not in ("open", "closed", "replay"):
        raise ValueError(f"traffic: loop {loop!r}: expected "
                         f"open|closed|replay")
    rng = random.Random(seed)
    shapes = _Shapes(traffic, seed)
    clients: list[list[dict]] = []
    if loop == "open":
        reqs = []
        for t in _arrivals(traffic["arrival"], rng, seconds):
            reqs.append({"due_s": round(t, 9), **shapes.draw(rng)})
        clients.append(reqs)
    else:
        n_clients = 1 if loop == "replay" else int(traffic["clients"])
        # more than any client can finish inside the window: one request
        # a second is several times what the measured systems complete
        per_client = int(seconds * float(
            traffic.get("max_requests_per_client_per_s", 1.0))) + 4
        # one sequence of draws dealt round the clients, so the requests
        # the clients START with are whole decks between them
        drawn = [{"due_s": None, **shapes.draw(rng)}
                 for _ in range(n_clients * per_client)]
        clients = [drawn[c::n_clients] for c in range(n_clients)]
    i = 0
    for reqs in clients:
        for r in reqs:
            r["id"] = i
            i += 1
    return {"loop": loop, "clients": clients}
