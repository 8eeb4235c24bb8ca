"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests, drawn from
the seed, is run through the plain float32 reference of the
configuration's model module (``forward`` of ``models/<model>.py``), one
sequence at a time: the prompt followed by
the served tokens.  The sample holds the longest request, then others in a
seeded order, one from each slot before a second from any, until it holds
the cell's ``check_tokens`` served tokens and ``check_requests`` requests.
At each served position the gap is the reference's best logit minus the
reference's logit of the token the program served; the number compared is
the widest gap.  It is zero where the program served the reference's
greedy token and grows with how far off the served token is.  The share
of served tokens that are not the reference's greedy choice is read beside
it: where a router's near tie in bfloat16 sends a token to another expert,
the widest gap swings as widely as the control's, and the share is the
number that separates them.  Each cell's file names the numbers it
compares and their limits; ``judge`` holds them to those limits.

The control, which the benchmark's runs do not compute, reads the same
numbers for the tokens that the reference in float8 (``precision="fp8"``)
puts first at each position, and is judged by the same limits
(``bench/control.py``).  Beside it, a witness for the tokens the program
serves off the reference's choice: the same numbers for the tokens the
reference with bfloat16 operands puts first, whether it puts the served
token first where the program is off, and the reference's router margin
at those positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import spec


def sample(recs, seed: int, min_tokens: int, min_requests: int) -> list:
    """Finished requests: the one with the longest sequence, then others in
    a seeded order, requests of slots not yet sampled first, until
    ``min_tokens`` served tokens and ``min_requests`` requests are held."""
    done = [r for r in recs if r.request.done and not r.failed
            and r.request.out_tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.prompt_len
                                       + len(r.request.out_tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    seen, first, later = {longest.slot}, [], []
    for i in order:
        (later if rest[i].slot in seen else first).append(rest[i])
        seen.add(rest[i].slot)
    out, n = [longest], len(longest.request.out_tokens)
    for r in first + later:
        if n >= min_tokens and len(out) >= min_requests:
            break
        out.append(r)
        n += len(r.request.out_tokens)
    return out


@functools.partial(jax.jit, static_argnames=("model", "cfg_items",
                                             "control"))
def _gaps(params, tokens, targets, valid, model, cfg_items, control):
    logits, margin = model.forward(params, tokens, cfg_items, "f32")
    best = jnp.max(logits, axis=-1)

    def gap_of(tok):
        picked = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
        return jnp.where(valid, best - picked, 0.0)

    served = gap_of(targets)
    if not control:
        return served, served, valid, margin, served
    low = model.forward(params, tokens, cfg_items, "fp8")[0]
    same = jnp.argmax(model.forward(params, tokens, cfg_items, "bf16")[0],
                      axis=-1).astype(jnp.int32)
    return (served, gap_of(jnp.argmax(low, axis=-1).astype(jnp.int32)),
            same == targets, margin, gap_of(same))


def gaps(params, cfg: dict, prompt, served, pad_to: int,
         control: bool = False) -> tuple[np.ndarray, ...]:
    """Per served token: the gap of the served token, the gap of the
    control's token, whether the bfloat16 reference puts the served token
    first, the reference's router margin, and the gap of the bfloat16
    reference's token.  The sequence is padded to
    ``pad_to`` so that one compiled program serves every request;
    causality keeps the padding out."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    n, p = len(served), len(prompt)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} > {pad_to}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(pad_to, np.int32)
    targets[p - 1:p - 1 + n] = served
    valid = np.zeros(pad_to, bool)
    valid[p - 1:p - 1 + n] = True
    model = spec.model_module(cfg)
    out = _gaps(params, jnp.asarray(tokens), jnp.asarray(targets),
                jnp.asarray(valid), model, model.model_keys(cfg), control)
    return tuple(np.asarray(x)[p - 1:p - 1 + n] for x in out)


def numbers(g: np.ndarray) -> dict:
    """The numbers compared, from the gaps of the tokens compared."""
    if not g.size:
        return {"max_logit_gap": float("nan"),
                "tokens_off_share": float("nan")}
    return {"max_logit_gap": float(g.max()),
            "tokens_off_share": float(np.count_nonzero(g > 0)) / g.size}


def judge(values: dict, limits: dict, tokens: int) -> tuple[bool, dict]:
    """Each limited number beside its limit, and whether all keep them."""
    checks = {name: {"value": values[name], "limit": limit}
              for name, limit in limits.items()}
    ok = tokens > 0 and all(c["value"] <= c["limit"]
                            for c in checks.values())
    return bool(ok), checks


def compare(params, cfg: dict, recs, seed: int, min_tokens: int,
            min_requests: int, pad_to: int, control: bool = False) -> dict:
    """The sample's numbers (and the control's and the witness's, when
    asked)."""
    picked = sample(recs, seed, min_tokens, min_requests)
    parts = [gaps(params, cfg, r.request.prompt, r.request.out_tokens,
                  pad_to, control) for r in picked]
    if parts:
        g, c, first, margin, b = (np.concatenate(x) for x in zip(*parts))
    else:
        g = c = first = margin = b = np.zeros(0)
    out = {"requests": len(picked), "tokens": int(g.size),
           "slots": len({r.slot for r in picked}), **numbers(g)}
    if control:
        off = g > 0

        def median(x):      # a dense model has no router: no margin
            x = x[np.isfinite(x)]
            return float(np.median(x)) if x.size else None

        out["control"] = numbers(c)
        out["witness"] = {
            **{f"bf16_{k}": v for k, v in numbers(b).items()},
            "tokens_off": int(np.count_nonzero(off)),
            "bf16_puts_served_first": int(np.count_nonzero(first[off])),
            "router_margin_median_off": median(margin[off]),
            "router_margin_median_all": median(margin)}
    return out
