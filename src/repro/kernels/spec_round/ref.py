"""Pure-jnp oracle for the speculative-round tree-descent kernel.

``descend_ref`` is the one descent rule of the sampler: at every level
both children of each lane's node are scored against its projector and
the lane goes left iff ``u * max(p_left + p_right, 1e-30) <= max(p_left,
0)``.  No mass is carried from one level to the next, so no level
inherits the rounding of the levels above it.  It is the Pallas kernel's
rule, the CPU path of ``ops.descend``, and (with the sharded
``child_scores`` of ``core.tree._descend_batch``) the mesh path's; the
golden-file suite pins its draws bit-for-bit, so changing an op order
here is a distribution change and must go through ``--regen-golden``
review.
"""
import jax
import jax.numpy as jnp


def child_scores(nodes: jax.Array, q: jax.Array, left: jax.Array):
    """<Q, node> of each lane's left child ``nodes[left]`` and of its right
    sibling ``nodes[left + 1]``: two (N,) rows."""
    return (jnp.einsum("nij,nij->n", q, nodes[left]),
            jnp.einsum("nij,nij->n", q, nodes[left + 1]))


def descend_ref(levels, q: jax.Array, us: jax.Array, *,
                scores=None) -> jax.Array:
    """Root-to-block traversal for N lanes in lockstep.

    levels: tuple of (2^lvl, R, R) node arrays (levels[0] is the root, not
    read); q: (N, R, R) conditioning projectors; us: (N, >= depth)
    uniforms.  Returns the chosen block index per lane (N,).
    ``scores(lvl, left)``: the two child scores at level ``lvl`` given each
    lane's left child (default ``child_scores`` on ``levels[lvl]``).
    """
    if scores is None:
        scores = lambda lvl, left: child_scores(levels[lvl], q, left)  # noqa: E731
    idx = jnp.zeros((q.shape[0],), jnp.int32)
    for lvl in range(1, len(levels)):
        p_left, p_right = scores(lvl, 2 * idx)
        go_left = us[:, lvl - 1] * jnp.maximum(p_left + p_right, 1e-30) \
            <= jnp.maximum(p_left, 0.0)
        idx = 2 * idx + jnp.where(go_left, 0, 1)
    return idx
