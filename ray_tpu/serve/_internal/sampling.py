"""Sampling parameters for the serve request path.

The host half of real sampling: a validated, immutable parameter set
that rides a request from serve/llm.py through the engine into the
macro plan, where it is compiled into the per-phase f32/i32 plan
arrays (temperature/top_k/top_p per slot, stop-token id rows padded
with -1) that models/paged.sample_tokens consumes device-side.

Greedy is temperature == 0.0 (the default), which keeps every
pre-sampling caller's behavior bit-identical: sample_tokens lowers to
argmax for those lanes, and a plan whose requests are all greedy is
still value-independent end to end.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

# fixed width of the device-side stop-id rows ((B, MAX_STOP_TOKENS) i32,
# -1 padded). A static bound keeps the jit cache keyed only on plan
# geometry; 4 covers eos + the usual chat-template stop ids.
MAX_STOP_TOKENS = 4


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode controls.

    temperature: 0.0 => greedy argmax (deterministic); > 0 scales logits
        before categorical sampling.
    top_k: keep only the k highest logits (0 => disabled/full vocab).
    top_p: nucleus sampling — keep the smallest set of tokens whose
        cumulative probability reaches top_p (1.0 => disabled).
    seed: per-request PRNG seed, or None (the default) to let the
        engine draw a fresh one per request — two seedless sampled
        requests must NOT share a token stream. With an explicit seed,
        sampling is reproducible per request REGARDLESS of
        co-scheduling: the slot's key is seeded from it at admission
        and split once per decode step, so batch composition never
        changes a request's tokens.
    stop: token ids that end generation early (the stop token itself is
        not delivered). Detected device-side; the host repairs its
        speculative plan when the resolved tokens reveal the stop.
    deadline: ABSOLUTE unix time (time.time() seconds) after which the
        result is worthless to the caller. Not a sampling control — it
        rides here because this dataclass is the per-request record
        that travels handle → replica → engine, and the engine's
        admission/shed policy is its consumer: requests still queued
        past their deadline are shed with a typed error instead of
        burning decode steps, and admission refuses requests whose
        queue ETA already overruns the budget. Request dicts set it via
        the relative ``deadline_s`` field (the handle stamps the
        absolute form so redispatch can't reset the clock).
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: "int | None" = None
    stop: Tuple[int, ...] = ()
    deadline: "float | None" = None

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        stop = tuple(int(t) for t in self.stop)
        if len(stop) > MAX_STOP_TOKENS:
            raise ValueError(
                f"at most {MAX_STOP_TOKENS} stop tokens supported, got {len(stop)}"
            )
        if any(t < 0 for t in stop):
            raise ValueError(f"stop token ids must be >= 0, got {stop}")
        object.__setattr__(self, "stop", stop)
        if self.deadline is not None and self.deadline <= 0:
            raise ValueError(
                f"deadline must be an absolute unix time > 0, got "
                f"{self.deadline} (request dicts carry the relative form "
                f"as 'deadline_s')"
            )

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    def stop_row(self) -> Tuple[int, ...]:
        """Fixed-width stop-id row for the device plan (-1 = unused)."""
        return self.stop + (-1,) * (MAX_STOP_TOKENS - len(self.stop))

    @classmethod
    def from_request(cls, obj) -> "SamplingParams":
        """Coerce a request-path value: None (greedy default), an
        existing SamplingParams, or a dict of fields."""
        if obj is None:
            return cls()
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, dict):
            return cls(**obj)
        raise TypeError(f"cannot build SamplingParams from {type(obj).__name__}")
