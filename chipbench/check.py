"""What decides ``correct``: the timed path's own outputs against the
float32 reference.

Once the window has closed and its requests have finished, the client
keeps serving the mix and ``Capture`` intercepts the compiled programs
that ``JaxBackend.execute`` calls, without changing them: a few prefill
calls, then ``K`` consecutive decode calls of the full device batch.
Around each decode call it keeps the slot each row writes (before and
after) and a fingerprint of every other slot. The reference then
re-computes those calls from the same inputs (``chipbench.reference``).

The numbers compared, each against its limit:

- ``decode_logit_gap``: over every served row of every captured decode
  call, the widest gap by which the reference logit of the token the
  program ranks first lies below the reference's best logit.
- ``decode_logit_rel``, ``prefill_logit_rel``: the relative L2 gap of the
  served rows' logits, and of the captured prefill calls' logits.
- ``cache_write_gap``: over the captured calls and the layers, the
  largest relative L2 gap between the K (or V) the program wrote to the
  rows' slots and the reference's.

A configuration compares the numbers it has limits for
(``check_limits``), and always the exact ones:

- ``cache_other_slots_changed``: (call, layer, row) triples whose cache
  changed outside the written slot.
- ``requests_wrong_length``: requests due in the window that did not
  finish with exactly their output length.
- ``window_compiles``: compilations from the ramp to the window's close.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

#: served tokens to compare at least, over the captured decode calls
DECODE_TOKENS = 256
#: prefill calls to capture (of two tokens or more)
PREFILL_CALLS = 2

EXACT = ("cache_other_slots_changed", "requests_wrong_length",
         "window_compiles")


def _kv(cache):
    """(k, v), each (L, B, T, Hkv, D): the decoder's stacked cache."""
    if cache.get("prefix"):
        raise ValueError("unrolled prefix layers are not covered")
    return cache["scanned"].k, cache["scanned"].v


@jax.jit
def _slots(k, v, slot):
    rows = jnp.arange(k.shape[1])
    return k[:, rows, slot], v[:, rows, slot]


@jax.jit
def _fingerprint(k, v, slot):
    """(L, B) position-weighted sums of the bits of every slot but the
    written one: equal before and after exactly when nothing else
    changed (up to a collision)."""
    def fp(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)
        t = a.shape[2]
        w = (jnp.arange(bits[0, 0].size, dtype=jnp.uint32)
             * jnp.uint32(2654435761) + 1).reshape(bits.shape[2:])
        keep = jnp.arange(t)[None, :] != slot[:, None]          # (B, T)
        return jnp.sum(bits * w * keep[None, :, :, None, None],
                       axis=(2, 3, 4), dtype=jnp.uint32)
    return fp(k), fp(v)


class Capture:
    """Intercepts the backend's compiled decode and prefill programs."""

    def __init__(self, backend):
        self.backend = backend
        self.k_calls = -(-DECODE_TOKENS // backend.max_batch)
        self.decode: List[dict] = []
        self.prefill: List[dict] = []
        self.final = None
        self._plan = None
        self._real_execute = backend.execute
        self._real_decode = backend._decode
        self._real_prefill = dict(backend._prefill)

    @property
    def done(self) -> bool:
        return (len(self.decode) >= self.k_calls
                and len(self.prefill) >= PREFILL_CALLS)

    def install(self) -> None:
        b = self.backend

        def execute(plan, f_mhz):
            self._plan = plan
            return self._real_execute(plan, f_mhz)

        def prefill_fn(fn):
            def run(params, toks):
                out = fn(params, toks)
                keep = len(self.prefill) < PREFILL_CALLS
                if keep and toks.shape[1] > 1:
                    self.prefill.append({"tokens": np.asarray(toks)[0],
                                         "logits": out[0]})
                return out
            return run

        b.execute = execute
        b._prefill = {n: (prefill_fn(fn), toks)
                      for n, (fn, toks) in self._real_prefill.items()}

    def install_decode(self) -> None:
        """From the next decode call on, keep ``k_calls`` consecutive
        calls; prefill calls are captured first, so that nothing runs
        after the last kept decode call."""
        t = self.backend.cache_len

        def decode(params, token, cache, pos):
            if len(self.decode) >= self.k_calls:
                return self._real_decode(params, token, cache, pos)
            slot = jnp.asarray(np.minimum(pos, t - 1))
            k, v = _kv(cache)
            fb = _fingerprint(k, v, slot)
            old = _slots(k, v, slot)
            logits, new = self._real_decode(params, token, cache, pos)
            k, v = _kv(new)
            self.decode.append({
                "token": np.asarray(token)[:, 0], "pos": np.asarray(pos),
                "served": len(self._plan.decode), "logits": logits[:, 0],
                "old": old, "new": _slots(k, v, slot),
                "fp_before": fb, "fp_after": _fingerprint(k, v, slot)})
            if len(self.decode) == self.k_calls:
                self.final = (k, v)
            return logits, new

        self.backend._decode = decode

    def uninstall(self) -> None:
        b = self.backend
        b.execute = self._real_execute
        b._decode = self._real_decode
        b._prefill = self._real_prefill


def _gap(ref, served):
    """Widest gap, over rows, between the reference's best logit and its
    logit of the token that ``served`` ranks first."""
    ref = jnp.asarray(ref, jnp.float32)
    top = jnp.argmax(jnp.asarray(served, jnp.float32), axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, top[..., None], axis=-1)[..., 0]
    return best - got


def _rel(a, b, axes):
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return (jnp.sqrt(jnp.sum((a - b) ** 2, axes))
            / jnp.sqrt(jnp.sum(b * b, axes)))


def readings(cap: Capture, weights, conf: dict,
             control: bool = False) -> Dict[str, float]:
    """The compared numbers of the captured calls. With ``control``, the
    int8 reference takes the program's place and is read the same way."""
    d = cap.decode
    tokens = np.stack([c["token"] for c in d])
    pos = np.stack([c["pos"] for c in d])
    old_k = jnp.stack([c["old"][0] for c in d], axis=1)
    old_v = jnp.stack([c["old"][1] for c in d], axis=1)
    fk, fv = cap.final
    ref, rk, rv = reference.decode(weights, conf, tokens, fk, fv, pos,
                                   old_k, old_v)
    if control:
        served, wk, wv = reference.decode(weights, conf, tokens, fk, fv,
                                          pos, old_k, old_v, control=True)
        wk, wv = jnp.stack(wk), jnp.stack(wv)
    else:
        served = jnp.stack([c["logits"] for c in d])
        wk = jnp.stack([c["new"][0] for c in d], axis=1)
        wv = jnp.stack([c["new"][1] for c in d], axis=1)
    rows = np.arange(pos.shape[1])[None, :] < np.array(
        [c["served"] for c in d])[:, None]
    dgap = _gap(ref, served)
    rk, rv = jnp.stack(rk), jnp.stack(rv)
    # (L, K) relative gaps over each call's rows
    wgap = jnp.maximum(_rel(wk, rk, (2, 3, 4)), _rel(wv, rv, (2, 3, 4)))
    prels = []
    for c in cap.prefill:
        pref = reference.prefill(weights, conf, c["tokens"])
        pserved = (reference.prefill(weights, conf, c["tokens"],
                                     control=True)
                   if control else c["logits"])
        prels.append(float(_rel(pserved, pref, None)))
    changed = sum(int(np.sum(np.asarray(c["fp_before"][i])
                             != np.asarray(c["fp_after"][i])))
                  for c in d for i in (0, 1))
    sr = np.nonzero(rows)
    return {
        "decode_logit_gap": float(jnp.max(dgap[sr])),
        "decode_logit_rel": float(_rel(jnp.asarray(served)[sr], ref[sr],
                                       None)),
        "prefill_logit_rel": max(prels),
        "cache_write_gap": float(jnp.max(wgap)),
        "cache_other_slots_changed": 0 if control else changed,
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Dict[str, dict]:
    """Each compared number beside its limit; exact ones have limit 0."""
    out = {}
    for name, value in numbers.items():
        if name in EXACT:
            out[name] = {"value": value, "limit": 0}
        elif name in limits:
            out[name] = {"value": value, "limit": limits[name]}
    return out


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
