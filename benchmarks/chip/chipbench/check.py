"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the one with the most output tokens, is
run through the configuration's plain reference: one forward pass over
each prompt followed by its served tokens.  At every served position the
reference's best logit minus its logit for the served token is a gap;
the widest gap over the sample is the number compared.  Greedy tokens
of an exact program read about 0, and rounding of near ties reads a
little more.

The control puts the same reference in the program's place with every
matrix product in float8 e4m3, both operands rounded (weights with one
scale per output channel, activations with one per row: the W8A8 path a
later change might take); at each position it reads the gap of the
token the rounded reference puts first.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: served tokens the sample should hold at least, and its most requests
SAMPLE_TOKENS = 768
SAMPLE_MAX = 32
#: sequences are padded to a multiple of this, so the reference compiles
#: for a few lengths only (padding sits after every compared position)
PAD = 1024


def _e4m3(a, axis: int):
    """Round through float8 e4m3 with one scale per slice along
    ``axis`` (the largest magnitude maps to 448, e4m3's largest)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class Fp8:
    """W8A8 rounding of a matrix product's operands."""

    @staticmethod
    def weight(w):
        return _e4m3(w, axis=-2)        # one scale per output channel

    @staticmethod
    def act(x):
        return _e4m3(x, axis=-1)        # one scale per row (token)


def sample(finished: Sequence, seed: int) -> List:
    """Requests to compare: the longest by served tokens, then others in
    an order drawn from ``seed`` until the sample holds SAMPLE_TOKENS."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.output_tokens), r.rid))
    rest = order[1:]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    picked = [order[0]]
    for i in rng.permutation(len(rest)):
        if (sum(len(r.output_tokens) for r in picked) >= SAMPLE_TOKENS
                or len(picked) >= SAMPLE_MAX):
            break
        picked.append(rest[int(i)])
    return picked


def _gaps(logits_fn: Callable, control: bool, params, toks, targets):
    lg = logits_fn(params, toks)
    best = lg.max(-1)
    gap = best - jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
    if not control:
        return gap, gap
    lq = logits_fn(params, toks, quant=Fp8)
    top = jnp.argmax(lq, -1)
    return gap, best - jnp.take_along_axis(lg, top[:, None], -1)[:, 0]


class Checker:
    def __init__(self, ref_module, cfg: dict, params, control: bool = False):
        logits_fn = functools.partial(ref_module.logits, cfg)
        self._fn = jax.jit(functools.partial(_gaps, logits_fn, control))
        self.params = params
        self.control = control

    def gaps(self, prompt: np.ndarray, served: Sequence[int]):
        """(program gaps, control gaps) at each served position of one
        request; ``prompt`` (P,) and its ``served`` tokens in order."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        seq = np.concatenate([prompt, np.asarray(served, np.int32)])
        n_in = len(seq) - 1
        size = -(-n_in // PAD) * PAD
        toks = np.zeros((size,), np.int32)
        toks[:n_in] = seq[:-1]
        targets = np.zeros((size,), np.int32)
        targets[:n_in] = seq[1:]
        gap, ctrl = self._fn(self.params, jnp.asarray(toks),
                             jnp.asarray(targets))
        lo, hi = len(prompt) - 1, n_in
        return (np.asarray(gap[lo:hi], np.float64),
                np.asarray(ctrl[lo:hi], np.float64))


def compare(checker: Checker, picked) -> Dict[str, float]:
    """Widest and mean gaps over the sample, and what was compared."""
    gaps, ctrls = [], []
    for r in picked:
        gap, ctrl = checker.gaps(np.asarray(r.prompt_tokens),
                                 r.output_tokens)
        gaps.append(gap)
        ctrls.append(ctrl)
    gap = np.concatenate(gaps) if gaps else np.zeros(0)
    ctrl = np.concatenate(ctrls) if ctrls else np.zeros(0)
    out = {"logit_gap_max": float(gap.max(initial=0.0)),
           "logit_gap_mean": float(gap.mean()) if gap.size else 0.0,
           "tokens": int(gap.size), "requests": len(picked)}
    if checker.control:
        out["control_gap_max"] = float(ctrl.max(initial=0.0))
        out["control_gap_mean"] = float(ctrl.mean()) if ctrl.size else 0.0
    return out
