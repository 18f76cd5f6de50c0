"""The one traffic generator. A mix is a JSON file under ``bench/traffic/``.

Every seed does the same work: lengths are the mix's lognormal
quantiles at ``(i + 0.5) / n`` and inter-arrival gaps the exponential
quantiles of the Poisson rate, each in one fixed shuffled order that no
seed changes. The seed draws the prompt token ids (and the weights).

Keys of a mix file:

- ``slots``, ``max_len``: the slot pool the cell serves with.
- ``arrivals``: ``{"kind": "poisson", "rate_per_s": r, "at_start": n}``
  (open loop: ``n`` requests due at t=0, then ``round(r * seconds)``
  more).
- ``prompt_tokens`` / ``output_tokens``: ``{"median", "sigma", "min",
  "max"}`` of a lognormal, clipped.
- ``stream``: ``null`` (full precision reached in set-up) or
  ``{"rate_bytes_per_s", "chunk_bytes", "latency_s"}``: the wire
  reaches the client by the wall clock from the window's start (see
  ``Link``).
- ``sample_requests``: how many of the requests that were served tokens
  the correctness check compares (the longest always among them).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np

ORDER_SEED = 0   # the one order of sizes and gaps, the same for every seed


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float        # seconds after the window opens
    prompt: np.ndarray  # (prompt_len,) int32
    out_len: int


def load_mix(name: str, root: Path) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose, any non-negative seed size."""
    return np.random.default_rng([stream, seed])


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified lognormal lengths: exp(mu + sigma * z_i) at the
    normal quantiles z_i = Phi^-1((i + 0.5) / n), rounded and clipped."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps of a Poisson
    process at ``rate``: -ln(1 - (i + 0.5) / n) / rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        return int(arr.get("at_start", 0)) + int(round(arr["rate_per_s"] * seconds))
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """The requests of one run, in due order: the same sizes and due
    times for every seed, prompt token ids from the seed."""
    n = n_requests(mix, seconds)
    order = rng_for(ORDER_SEED, 1)
    p_len = order.permutation(lognormal_quantiles(mix["prompt_tokens"], n))
    o_len = order.permutation(lognormal_quantiles(mix["output_tokens"], n))
    arr = mix["arrivals"]
    due = np.zeros(n)
    k = int(arr.get("at_start", 0))
    due[k:] = np.cumsum(order.permutation(exponential_gaps(arr["rate_per_s"], n - k)))
    toks = rng_for(seed, 2)
    return [Request(rid=i, due_s=float(due[i]),
                    prompt=toks.integers(0, vocab, int(p_len[i]), dtype=np.int32),
                    out_len=int(o_len[i]))
            for i in range(n)]


class Link:
    """The wire's transport, by the wall clock: bytes leave the sender at
    ``rate_bytes_per_s`` from ``latency_s`` on, and the receiver may take
    every whole ``chunk_bytes`` that has arrived (the last chunk may be
    short). A receiver slower than the link falls behind; the bytes wait
    for it."""

    def __init__(self, spec: dict, total: int):
        self.rate = float(spec["rate_bytes_per_s"])
        self.chunk = int(spec["chunk_bytes"])
        self.latency = float(spec["latency_s"])
        self.total = total
        self.taken = 0

    def arrived(self, t: float) -> float:
        return min(float(self.total), max(0.0, self.rate * (t - self.latency)))

    def available(self, t: float) -> int:
        """Bytes the receiver can take at ``t``: whole chunks that have
        arrived, or all that is left once the whole wire has."""
        got = self.arrived(t)
        if got >= self.total:
            return self.total - self.taken
        return max(0, int((got - self.taken) // self.chunk) * self.chunk)

    def take(self, n: int) -> None:
        self.taken += n

    def next_s(self) -> float | None:
        """When the next chunk will have arrived (None: the wire is all
        taken)."""
        if self.taken >= self.total:
            return None
        return self.latency + min(self.total, self.taken + self.chunk) / self.rate
