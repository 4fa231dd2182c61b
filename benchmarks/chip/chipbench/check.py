"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and always holding
the longest, is packed into one row and run through the reference
(``reference/<family>.py``) layer by layer, with weights it draws itself
from the seed. At every served token the reference's best logit is
compared with the logit of the token the program served; the widest gap
is the number compared. With greedy decoding a sound program serves the
reference's best token up to near-ties, so its gap stays at the size of
its rounding.

The control is the reference with every weight matrix cast to float8
(e4m3, one scale per output channel), the precision below bf16: at the
same positions, the gap of the token that the control puts first.
"""
from __future__ import annotations

import numpy as np

from chipbench import weights as W

CAP = 4096  # packed row: the chat mix's longest prompt + answer fits
MAX_REQUESTS = 16

MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj")


def sample(finished: list[tuple[np.ndarray, list[int]]], seed: int, cap: int = CAP) -> list[int]:
    """Indices of the requests to compare: the longest first, then others
    in an order drawn from the seed, while the packed row has room."""
    if not finished:
        return []
    size = [len(p) + len(t) - 1 for p, t in finished]
    longest = int(np.argmax(size))
    order = [longest] + [
        int(i) for i in np.random.default_rng([seed, 7]).permutation(len(finished)) if i != longest
    ]
    out, used = [], 0
    for i in order:
        if used + size[i] <= cap and len(out) < MAX_REQUESTS:
            out.append(i)
            used += size[i]
    return out


def pack(items: list[tuple[np.ndarray, list[int]]], cap: int = CAP):
    """One row of prompt ++ served tokens (the last served token is never
    fed back), its positions and segments, the rows whose logits predict a
    served token, and those tokens."""
    tokens = np.zeros(cap, np.int32)
    pos = np.zeros(cap, np.int32)
    seg = np.full(cap, -1, np.int32)
    rows, served, at = [], [], 0
    for k, (prompt, toks) in enumerate(items):
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        n = len(seq)
        tokens[at:at + n], pos[at:at + n], seg[at:at + n] = seq, np.arange(n), k
        rows.extend(range(at + len(prompt) - 1, at + n))
        served.extend(toks)
        at += n
    return tokens, pos, seg, np.asarray(rows, np.int32), np.asarray(served, np.int32)


def fp8_matrices(w: dict) -> dict:
    """Weight matrices through float8 e4m3 with one scale per output
    channel (input axes reduced), back in float32."""
    import jax.numpy as jnp

    out = dict(w)
    for name in MATRICES + ("lm_head",):
        if name not in w:
            continue
        a = w[name]
        red = (0,) if name not in ("o_proj", "down_proj") else tuple(range(a.ndim - 1))
        amax = jnp.maximum(jnp.max(jnp.abs(a), axis=red, keepdims=True), 1e-12)
        scale = amax / 448.0
        out[name] = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return out


def logit_gaps(ref, m: W.Dims, seed: int, tokens, pos, seg, rows, served, control: bool = False):
    """Per served token: the reference's best logit minus the served
    token's; with ``control``, also the gap of the control's first choice."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    base = jax.device_put(W.base_key(seed), dev)
    put = lambda a: jax.device_put(np.asarray(a), dev)
    tokens, pos, seg, rows = put(tokens), put(pos), put(seg), put(rows)
    g = W.globals_f32(base, m)
    x = ref.embed(g, tokens)
    xc = x
    for i in range(m.layers):
        w = W.layer(base, m, jnp.int32(i))
        x = ref.layer(w, x, pos, seg, eps=m.eps, theta=m.theta)
        if control:
            xc = ref.layer(fp8_matrices(w), xc, pos, seg, eps=m.eps, theta=m.theta)
        del w
    logits = ref.head(g, x, rows, eps=m.eps)[:, : m.vocab]
    best = jnp.max(logits, axis=-1)
    gap = np.asarray(best - jnp.take_along_axis(logits, put(served)[:, None], axis=-1)[:, 0])
    if not control:
        return gap, None
    lc = ref.head(fp8_matrices(g), xc, rows, eps=m.eps)[:, : m.vocab]
    pick = jnp.argmax(lc, axis=-1)
    gap_c = np.asarray(best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0])
    return gap, gap_c
