"""The comparison that decides ``correct`` for a served model.

After the window, a sample of the finished requests, drawn from the seed
and holding the longest, is run through the plain float32 reference: once
over each prompt followed by its served tokens. At every served position
the number read is the gap by which the served token's reference logit
lies below the reference's best logit there (0 where they agree). The
compared number is the mean gap over the sample's served tokens; the
widest gap is reported beside it.

Why the mean and not the widest: with random weights at published widths
the top two logits of a 152k vocabulary often lie within a few hundredths
of each other, so both the bf16 program and the int8 control flip near
ties, and the widest gap over some 600 tokens reads 0.04-0.08 for the
program and 0.22-0.34 for the control, less than three times apart. The
mean grows with the square of the error (how often a tie flips times how
far), and so separates the two (PERF.md, section 2).

The control puts the reference in the program's place at the precision
below the configuration's (``int8=True``): at the same positions it reads
the gap of the token that the control would put first, and that gap goes
through the same limit, so that ``correct`` comes out false.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SAMPLE_TOKENS = 600   # served tokens to check per run, at least
SAMPLE_MAX = 12       # requests at most
MIN_CHECKED = 200     # fewer served tokens read than this: not correct


def sample(finished: Sequence, seed: int) -> List:
    """The longest finished request (by served tokens, then prompt), then
    others drawn from the seed until the sample holds ``SAMPLE_TOKENS``
    served tokens or ``SAMPLE_MAX`` requests."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (len(r.output), len(r.prompt)))
    picked = [by_len[-1]]
    rng = np.random.default_rng([int(seed), 7])
    for i in rng.permutation(len(by_len) - 1):
        if (sum(len(r.output) for r in picked) >= SAMPLE_TOKENS
                or len(picked) >= SAMPLE_MAX):
            break
        picked.append(by_len[i])
    return picked


class Reference:
    """Jitted reference passes of one family, over sequences padded to one
    length, reading ``rows`` served positions of each."""

    def __init__(self, cfg: dict, pad_to: int, rows: int):
        mod = importlib.import_module(f"reference.{cfg['family']}")
        # room after the longest sequence, so that the rows read from any
        # start lie inside the padded sequence
        self.pad_to, self.rows = pad_to + rows, rows

        def stats(params, toks, ids, first, int8):
            h = mod.hidden(params, toks, cfg, int8)
            return mod.head(lax.dynamic_slice_in_dim(h, first, rows),
                            params, ids, int8)

        def gaps(params, toks, ids, first):
            best, at, _ = stats(params, toks, ids, first, False)
            return best - at

        def control_ids(params, toks, first):
            return stats(params, toks, jnp.zeros(rows, jnp.int32), first,
                         True)[2]

        # the reference is the benchmark's and takes nothing of the
        # program, its compile accounting included
        # dalek: allow[bare-jit] the benchmark's reference, not the system
        self._gaps = jax.jit(gaps)
        # dalek: allow[bare-jit] the benchmark's control, not the system
        self._control = jax.jit(control_ids)

    def gap_stats(self, params, reqs, control: bool = False) -> dict:
        """Mean and widest reference gap at the served positions of ``reqs``
        (or, with ``control``, at the control's first choices there), and
        how many served tokens were read."""
        worst, total, n = 0.0, 0.0, 0
        for r in reqs:
            out = np.asarray(r.output, np.int32)
            seq = np.concatenate([np.asarray(r.prompt, np.int32), out[:-1]])
            if len(seq) + self.rows > self.pad_to or len(out) > self.rows:
                raise ValueError(f"request of {len(seq)} + {len(out)} tokens "
                                 f"exceeds the reference's sizes")
            toks = np.zeros(self.pad_to, np.int32)
            toks[:len(seq)] = seq
            toks = jnp.asarray(toks)
            first = jnp.int32(len(r.prompt) - 1)
            ids = np.zeros(self.rows, np.int32)
            ids[:len(out)] = out
            ids = jnp.asarray(ids)
            if control:
                ids = self._control(params, toks, first)
            # dalek: allow[host-sync] one fetch per checked request, after the window
            g = np.asarray(self._gaps(params, toks, ids, first))
            worst = max(worst, float(g[:len(out)].max()))
            total += float(g[:len(out)].astype(np.float64).sum())
            n += len(out)
        return {"mean": total / n if n else float("inf"), "max": worst,
                "tokens": n}


def compared(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": value, "limit": limit}


def passes(checks: List[dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks)
