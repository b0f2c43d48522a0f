"""What ``sdar-serve-reason-c1`` compares and its controls: a served
request's whole trajectory teacher-forced through the plain reference
(``benchmark/reference_sdar.py``) in ONE call, the two kinds of gap that
decide ``correct``, and the planted faults that have to fail them, beside
the float8 control (``benchmark/runners/serve_sdar.py`` ``reference_gaps``,
``benchmark/tools/calibrate.py``, ``benchmark/tests/test_serve_sdar.py``).

**The trajectory.** A completion carries, for every position of the
blocks it generated, the token and the pass of its block that unmasked
it (``Completion.tokens + cut``, ``Completion.passes``). From them
:func:`trajectory` lays out the rows of one forward: the committed
sequence (the prompt, then every block's final tokens) as copy 0, and
behind it, as copies 1, 2, ..., every generated block as it stood before
its pass 0, 1, ...: its known tokens and ``[MASK]`` where the pass found
it masked, at the block's own positions. Under the reference's mask (a
row sees copy 0's earlier blocks and its own block in its own copy) a
row of copy ``p + 1`` computes the logits that pass ``p`` of its block
computed against the cache: nothing is generated twice, and nothing is
compared that the timed run did not serve.

**The gaps.** ``token``: at every position, in the pass that unmasked
it, how far the served token's logit lies under the reference's best.
``choice``: at every pass that left something masked, how far the
reference's log-confidence at the worst position the program chose lies
under the reference's ``count``-th best still-masked one (0 where the
program chose the reference's own). Both are taken by the widest and by
the 99th percentile, as ``serve_xing.reference_gaps`` says why.

**The controls** are teacher forced too, on the served trajectory: what
each would serve at the served positions (its own first token) and
choose at the served passes (its own ``count`` surest), read against the
sound float32 reference. A fault is a changed input
(:func:`trajectory`'s ``fault``), a function of the reference swapped
while one forward is traced (:func:`forward`), or a changed reading of
the sound logits; never an option of the reference.
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark import reference_sdar

# the block's later positions hidden from its earlier ones (the
# autoregressive mask a block-causal one replaces); a cache that kept what
# the block's last denoising pass wrote (two of four positions the keys
# of [MASK]) because the commit pass was skipped; the positions ranked by
# their largest logit and not by its probability; [MASK] embedded as
# token 0
FAULTS = ("causal_inside_block", "commit_skipped", "confidence_from_logit",
          "mask_as_token_0")
# those whose forward pass differs from the sound one's (by its function
# or by its input); the other reads the sound logits otherwise
RUN_APART = ("causal_inside_block", "commit_skipped", "mask_as_token_0")


def trajectory(prompt, generated, passes, cfg, length=None, fault=None):
    """The rows of the one forward that teacher-forces a served request
    (module docstring): ``tokens``, ``positions``, ``blocks``, ``copies``
    (each (rows,) int32, padded to ``length`` with rows nothing sees) and
    ``reads``, one entry for each pass of each generated block in the
    order served: ``(rows of the block's copy, masked (block,) bool,
    chosen (block,) bool, the served ids (block,))``.

    ``generated``: the ids of every generated position (the answer and
    what was cut of its last block); ``passes``: for each the pass of its
    block that unmasked it. ``fault``: ``"commit_skipped"`` or
    ``"mask_as_token_0"`` (the two that change the input)."""
    block, mask_id = cfg["block_len"], cfg["mask_id"]
    start = len(prompt) - len(prompt) % block
    final = np.asarray(list(prompt) + list(generated), np.int64)
    total = len(final)
    assert total % block == 0, (len(prompt), len(generated))
    # -1: known from the prompt
    when = np.concatenate([np.full(len(prompt), -1), np.asarray(passes)])
    committed = final.copy()
    if fault == "commit_skipped":
        # a block's columns are what its LAST denoising pass wrote: the
        # positions that pass unmasked were [MASK] when it ran
        for first in range(start, total, block):
            here = slice(first, first + block)
            committed[here] = np.where(when[here] == when[here].max(),
                                       mask_id, final[here])
    at = np.arange(total)
    tokens, positions, copies, reads = [committed], [at], \
        [np.zeros(total, np.int64)], []
    rows = total
    for first in range(start, total, block):
        here = slice(first, first + block)
        for number in range(when[here].max() + 1):
            masked = when[here] >= number
            state = np.where(masked, 0 if fault == "mask_as_token_0"
                             else mask_id, final[here])
            tokens.append(state)
            positions.append(at[here])
            copies.append(np.full(block, number + 1))
            reads.append((np.arange(rows, rows + block), masked,
                          when[here] == number, final[here]))
            rows += block
    tokens, positions, copies = map(np.concatenate,
                                    (tokens, positions, copies))
    blocks = positions // block
    pad = (length or rows) - rows
    assert pad >= 0, (rows, length)
    fill = lambda a, value: np.pad(a, (0, pad), constant_values=value) \
        .astype(np.int32)
    return (fill(tokens, 0), fill(positions, 0), fill(blocks, -1),
            fill(copies, -1), reads)


def rows_needed(prompt_max, new_max, cfg):
    """Rows of the longest trajectory: a prompt of ``prompt_max`` with an
    answer of ``new_max`` (rounded up to blocks) and the copies of its
    blocks before each denoising pass, as a multiple of the reference's
    row block."""
    block = cfg["block_len"]
    generated = new_max + 2 * block
    rows = prompt_max + generated * (1 + cfg["denoising_steps"])
    return -(-rows // reference_sdar.ROW_BLOCK) * reference_sdar.ROW_BLOCK


@contextlib.contextmanager
def _swapped(fault):
    sound = reference_sdar.attention

    def causal_inside(q, k, v, blocks, copies):
        # a row sees only the rows before it: inside a block and a copy
        # the rows stand in position order
        import jax
        import jax.numpy as jnp

        rows, heads, d = q.shape
        per = heads // k.shape[1]
        at = jnp.arange(rows)

        def one(q_b, block_b, copy_b, at_b):
            q_b = q_b.reshape(-1, heads // per, per, d)
            s = jnp.einsum("tgrd,sgd->grts", q_b, k,
                           precision=reference_sdar.HIGHEST) * d ** -0.5
            seen = ((copies[None, :] == 0)
                    & (blocks[None, :] < block_b[:, None])) \
                | ((copies[None, :] == copy_b[:, None])
                   & (blocks[None, :] == block_b[:, None])
                   & (at[None, :] <= at_b[:, None]))
            s = jnp.where(seen[None, None], s, -jnp.inf)
            o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v,
                           precision=reference_sdar.HIGHEST)
            return o.reshape(-1, heads, d)

        return reference_sdar._blocks(
            one, [q, blocks, copies, at],
            min(reference_sdar.QUERY_BLOCK, rows))

    if fault == "causal_inside_block":
        reference_sdar.attention = causal_inside
    try:
        yield
    finally:
        reference_sdar.attention = sound


def forward(cfg, precision="f32", fault=None):
    """``(params, tokens, positions, blocks, copies, rows, picks) ->
    (best, first, sure, picked)`` of the reference over one trajectory,
    jitted, a function of its own for each ``precision`` and ``fault``:
    at each of ``rows`` the largest logit, its token, that token's
    probability ``softmax(logits)[argmax]`` and the logits of the tokens
    ``picks`` (any, rows) names there. The (rows, vocab) logits stay on
    the device."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    frozen = reference_sdar.frozen(cfg)

    def run(params, tokens, positions, blocks, copies, rows, picks):
        with _swapped(fault):
            logits = reference_sdar.forward(
                params, tokens, frozen, precision, rows, positions, blocks,
                copies)
        picked = jnp.take_along_axis(logits, picks.T, axis=-1).T
        return (logits.max(-1), logits.argmax(-1),
                reference_sdar.confidence(logits), picked)

    return jax.jit(run)


def gaps(reads, best, sure, picked, chosen_by=None):
    """The two kinds of gap (module docstring) of one trajectory.
    ``reads``: :func:`trajectory`'s; ``best``/``sure``: the sound
    reference's largest logit and its probability at every read row (in
    ``reads``' order, a block a pass); ``picked``: the sound reference's
    logit of the token served (or that a control would serve) there.
    ``chosen_by``: a control's own ranking of the read rows (any array
    of their shape: it chooses its ``count`` largest among a pass's
    masked positions, ties to the lower); ``None``: the served choice.
    Returns (token gaps, choice gaps), numpy."""
    token, choice = [], []
    log_sure = np.log(np.asarray(sure, np.float64))
    for i, (_, masked, chosen, _) in enumerate(reads):
        here = slice(i * len(masked), (i + 1) * len(masked))
        count = int(chosen.sum())
        took = chosen if chosen_by is None else reference_sdar.choose(
            np.asarray(chosen_by[here]), masked, count)
        # the served positions' tokens, whatever a control would choose
        token.append((np.asarray(best[here]) - np.asarray(picked[here]))
                     [chosen])
        if masked.sum() > count:
            among = np.sort(log_sure[here][masked])[::-1]
            choice.append(max(0.0, among[count - 1]
                              - log_sure[here][took].min()))
    return np.concatenate(token), np.asarray(choice)
