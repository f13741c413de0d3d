"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished is drawn from the seed, the longest of
them always in it.  The reference runs once over each prompt followed by
its served tokens (greedy decoding), and for every served token reads the
gap by which that token's reference logit lies below the reference's
best logit at its position.  The number compared is the widest such gap.
A served token that the reference ranks first has gap 0; a token altered
where it is produced, or a wrong cache, puts a token far down.

The control (``control_gaps``, run by ``bench/tools/calibrate.py`` and the
tests, never by a benchmark run) is the same reference in float8 weights:
at every position of the same sequences it reads the gap of the token
that the control ranks first.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"
ROW_BLOCK = 256


def reference_class(name: str):
    """The ``Reference`` of the module a configuration names
    (``bench/configs/<name>.py``), a class with this interface:

    - ``Reference(model, seed, *, quant="")``: the plain float32 model of
      the configuration's ``model`` block, its weights regenerated from
      ``seed`` by ``bench.weights``; ``quant`` names the lower-precision
      control (``""`` is the reference itself);
    - ``hidden(tokens)``: (B, S) token ids to (B, S, d) hidden states
      after the last layer;
    - ``logits(hrows)``: (R, d) hidden rows to (R, vocab) float32 logits,
      the final norm and the output head applied.
    """
    path = CONFIG_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Reference


def draw_sample(records, k: int, seed: int) -> list:
    """``k`` finished requests drawn from ``seed``: the longest (prompt
    plus served tokens) and ``k - 1`` others."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.tokens),
                                       -r.req.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(sample) -> list:
    """(prompt ids, served ids) per sampled request."""
    return [(np.asarray(r.req.prompt, np.int32),
             np.asarray(r.tokens, np.int32)) for r in sample]


def _layout(seqs, k: int, length: int):
    """Token matrix (k, length), zero-padded at the end (causal, so the
    padding changes no earlier position), and the (row, position, target)
    of every served token."""
    tok = np.zeros((k, length), np.int32)
    rows, pos, tgt = [], [], []
    for i, (prompt, served) in enumerate(seqs):
        seq = np.concatenate([prompt, served])[:length]
        tok[i, :len(seq)] = seq
        p = len(prompt)
        for j, t in enumerate(served):
            rows.append(i)
            pos.append(p - 1 + j)
            tgt.append(int(t))
    return tok, np.asarray(rows), np.asarray(pos), np.asarray(tgt)


def _blocks(lm, hidden, rows, pos):
    """Yield (slice, logits) over the hidden rows of the served tokens."""
    import jax.numpy as jnp
    n = len(rows)
    for lo in range(0, n, ROW_BLOCK):
        hi = min(lo + ROW_BLOCK, n)
        idx_r = np.zeros(ROW_BLOCK, np.int32)
        idx_p = np.zeros(ROW_BLOCK, np.int32)
        idx_r[:hi - lo], idx_p[:hi - lo] = rows[lo:hi], pos[lo:hi]
        h = hidden[jnp.asarray(idx_r), jnp.asarray(idx_p)]
        yield slice(lo, hi), np.asarray(lm.logits(h))[:hi - lo]


def served_gaps(lm, seqs, k: int, length: int) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    tok, rows, pos, tgt = _layout(seqs, k, length)
    if not len(rows):
        return np.zeros(0)
    hidden = lm.hidden(tok)
    out = np.zeros(len(rows))
    for sl, logits in _blocks(lm, hidden, rows, pos):
        t = tgt[sl]
        out[sl] = logits.max(axis=1) - logits[np.arange(len(t)), t]
    return out


def first_choices(lm, seqs, k: int, length: int) -> np.ndarray:
    """Per served-token position: the token ``lm`` ranks first."""
    tok, rows, pos, _ = _layout(seqs, k, length)
    if not len(rows):
        return np.zeros(0, np.int32)
    hidden = lm.hidden(tok)
    out = np.zeros(len(rows), np.int32)
    for sl, logits in _blocks(lm, hidden, rows, pos):
        out[sl] = logits.argmax(axis=1)
    return out


def control_gaps(ref, ctl, seqs, k: int, length: int) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of
    the token the control ranks first."""
    picks = first_choices(ctl, seqs, k, length)
    # the served tokens stay the context; the control's picks are scored
    tok, rows, pos, _ = _layout(seqs, k, length)
    if not len(rows):
        return np.zeros(0)
    hidden = ref.hidden(tok)
    out = np.zeros(len(rows))
    for sl, logits in _blocks(ref, hidden, rows, pos):
        t = picks[sl]
        out[sl] = logits.max(axis=1) - logits[np.arange(len(t)), t]
    return out
