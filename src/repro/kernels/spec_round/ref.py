"""Pure-jnp oracle for the speculative-round tree-descent kernel.

``descend_ref`` is the arithmetic the CPU CI actually executes for the
rejection hot path: it must stay expression-for-expression identical to
``core.tree._descend_batch``'s unsharded branch, because the golden-file
suite pins the sampler's draws bit-for-bit.  Changing an op order here is
a distribution change and must go through ``--regen-golden`` review.

``descend_pair_ref`` is the Pallas kernel's own rule (both children
scored at every level, no mass carried), for the kernel tests only.
"""
import jax
import jax.numpy as jnp

#: levels whose whole node set is scored with one stacked matmul instead
#: of per-lane gathers — must match ``core.tree._SHALLOW_MAX`` (the plain
#: and sharded descents classify levels by the same global node count;
#: tests assert the two constants agree)
_SHALLOW_MAX = 32


def descend_ref(levels, q: jax.Array, us: jax.Array) -> jax.Array:
    """Root-to-block traversal for N lanes in lockstep (unsharded).

    levels: tuple of (2^lvl, R, R) node arrays (levels[0] is the root);
    q: (N, R, R) conditioning projectors; us: (N, depth) uniforms.
    Returns the chosen block index per lane (N,).  Shallow levels are
    scored against every node with one stacked (nodes, R^2) x (R^2, N)
    matmul; deep levels gather the left child per lane.  The parent's
    mass is carried down (p_child = p_left or p_all - p_left).
    """
    n = q.shape[0]
    r = q.shape[-1]
    idx = jnp.zeros((n,), jnp.int32)
    depth = len(levels) - 1
    shallow = [lvl for lvl in range(1, depth + 1)
               if (1 << lvl) <= _SHALLOW_MAX]
    p_all = jnp.einsum("ij,nij->n", levels[0][0], q)
    offs = {}
    if shallow:
        stacked = jnp.concatenate(
            [levels[lvl].reshape(-1, r * r) for lvl in shallow]
        )                                            # (sum 2^lvl, R^2)
        all_scores = stacked @ q.reshape(n, r * r).T  # (sum 2^lvl, N)
        off = 0
        for lvl in shallow:
            offs[lvl] = off
            off += levels[lvl].shape[0]
    for lvl in range(1, depth + 1):
        nodes = levels[lvl]
        if lvl in offs:
            s_l = all_scores[offs[lvl]:offs[lvl] + nodes.shape[0]]
            p_left = jnp.take_along_axis(s_l.T, (2 * idx)[:, None],
                                         axis=1)[:, 0]
        else:
            left = nodes[2 * idx]                   # (N, R, R) gather
            p_left = jnp.einsum("nij,nij->n", q, left)
        go_left = us[:, lvl - 1] * jnp.maximum(p_all, 1e-30) \
            <= jnp.maximum(p_left, 0.0)
        idx = 2 * idx + jnp.where(go_left, 0, 1)
        p_all = jnp.maximum(jnp.where(go_left, p_left, p_all - p_left), 0.0)
    return idx


def descend_pair_ref(levels, q: jax.Array, us: jax.Array) -> jax.Array:
    """The descent kernel's rule, for tests: at every level both children
    of each lane's node are scored and the lane goes left iff
    ``u * max(p_left + p_right, 1e-30) <= max(p_left, 0)``.  Same
    arguments and result as ``descend_ref``; the root is not read."""
    idx = jnp.zeros((q.shape[0],), jnp.int32)
    for lvl in range(1, len(levels)):
        nodes = levels[lvl]
        p_left = jnp.einsum("nij,nij->n", q, nodes[2 * idx])
        p_right = jnp.einsum("nij,nij->n", q, nodes[2 * idx + 1])
        go_left = us[:, lvl - 1] * jnp.maximum(p_left + p_right, 1e-30) \
            <= jnp.maximum(p_left, 0.0)
        idx = 2 * idx + jnp.where(go_left, 0, 1)
    return idx
