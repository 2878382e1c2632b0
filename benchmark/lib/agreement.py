"""The comparison that decides ``correct`` for served tokens, whatever the
family: a family's plain reference hands its ``forward`` to it."""

from __future__ import annotations

import numpy as np


def rows_that_chose(forward, params, prompts, served) -> list:
    """Teacher-forced: for each (prompt, served tokens) pair the logits
    ``forward`` gives at the positions that chose each served token, a
    [tokens, vocab] array a pair.  Sequences are padded to one length and
    run as one batch: one program."""
    import jax
    import jax.numpy as jnp
    total = max(len(p) + len(t) for p, t in zip(prompts, served))
    most = max(len(t) for t in served)
    batch = np.zeros((len(prompts), total), np.int32)
    # the logits at position len(p)-1+j chose served token j
    chose = np.zeros((len(prompts), most), np.int32)
    for r, (p, t) in enumerate(zip(prompts, served)):
        batch[r, :len(p)] = p
        batch[r, len(p):len(p) + len(t)] = t
        chose[r] = np.minimum(len(p) - 1 + np.arange(most), total - 1)

    def rows(params, tokens, at):       # gathered on the device: one program
        return jnp.take_along_axis(forward(params, tokens),
                                   at[:, :, None], axis=1)
    logits = np.asarray(jax.jit(rows)(params, jnp.asarray(batch),
                                      jnp.asarray(chose)))
    return [logits[r, :len(t)] for r, t in enumerate(served)]


def tokens_agree(forward, params, prompts, served, rtol: float,
                 program_logits=None, logit_rms_limit=None) -> dict:
    """Teacher-force each (prompt, served tokens) pair through the
    reference's ``forward(params, tokens [B, S]) -> logits [B, S, vocab]``
    and hold the program to it in two numbers.

    ``worst_gap``: every served token is the reference's choice, or tied
    with it within ``2 * rtol`` of the logit scale.  A wrong page, mask or
    position in the engine moves logits by their whole spread, far outside
    a tie; rounding does not show here (random weights leave few near
    ties), so this number does not tell 8-bit weights from bf16.

    ``logit_rms``: ``program_logits`` (one [tokens, vocab] array a prompt:
    the logits the program's own bodies gave where they chose each served
    token) against the reference's, as the root mean square of the
    difference over the standard deviation of the reference's logits.  It
    averages over tokens x vocab values, so it is steady from seed to seed
    and reads the precision the program computed in; ``logit_rms_limit``
    lies between what bf16 compute reads and what 8-bit weights read.
    """
    chose = rows_that_chose(forward, params, prompts, served)
    scale = max(float(np.abs(rows).max()) for rows in chose)
    worst, compared, identical = 0.0, 0, 0
    for rows, t in zip(chose, served):
        chosen = rows[np.arange(len(t)), np.asarray(t)]
        gap = rows.max(-1) - chosen
        worst = max(worst, float(gap.max()))
        compared += len(t)
        identical += int((gap == 0).sum())
    finite = all(bool(np.isfinite(rows).all()) for rows in chose)
    said = {"ok": finite and worst <= 2 * rtol * scale,
            "tokens_compared": compared, "greedy_identical": identical,
            "worst_gap": worst, "logit_scale": scale,
            "allowed_gap": 2 * rtol * scale}
    if program_logits is not None:
        ref = np.concatenate(chose).astype(np.float64)
        got = np.concatenate([np.asarray(a, np.float64)
                              for a in program_logits])
        rms = float(np.sqrt(np.mean((got - ref) ** 2)) / ref.std())
        said.update(logit_rms=rms, logit_rms_limit=logit_rms_limit,
                    logit_max=float(np.abs(got - ref).max() / scale))
        said["ok"] = bool(said["ok"] and np.isfinite(rms)
                          and rms <= logit_rms_limit)
    return said


def greedy_tokens(forward, params, prompts, new_tokens: int) -> list:
    """What a system that computed ``forward`` would serve: each prompt's
    next ``new_tokens`` tokens by greedy choice, with no cache (the whole
    padded batch again for every token: one program)."""
    import jax
    import jax.numpy as jnp
    total = max(len(p) for p in prompts) + new_tokens
    batch = np.zeros((len(prompts), total), np.int32)
    for r, p in enumerate(prompts):
        batch[r, :len(p)] = p
    step = jax.jit(forward)
    for j in range(new_tokens):
        logits = step(params, jnp.asarray(batch))
        for r, p in enumerate(prompts):
            batch[r, len(p) + j] = int(jnp.argmax(logits[r, len(p) - 1 + j]))
    return [batch[r, len(p):len(p) + new_tokens].tolist()
            for r, p in enumerate(prompts)]


def with_weights_at(forward, bits: int):
    """The control: ``forward`` with every weight matrix rounded to a
    signed ``bits``-bit grid, one scale per output column (8: the int8
    weights a later PR might serve; 4: the step below)."""
    import jax
    import jax.numpy as jnp

    def rounded(w):
        if w.ndim < 2:
            return w
        top = 2 ** (bits - 1) - 1
        scale = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)),
                        keepdims=True) / top
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(w / scale) * scale

    def control(params, tokens):
        return forward(jax.tree_util.tree_map(rounded, params), tokens)
    return control
