"""Plain reference of the NDPP rejection sampler, for the comparison that
decides ``correct``.

It imports nothing of the program and takes nothing the program made: it
starts from the catalog's raw factors (V, B, D), which the benchmark makes
from the seed, and builds everything else itself.

- The proposal kernel is ``Lhat = V V^T + |S|`` with ``S = B (D - D^T) B^T``
  and ``|S|`` the matrix absolute value (Han et al., ICLR 2022, Sec. 4.1:
  the Youla pairs ``sigma_j (y1 y1^T + y2 y2^T)``).  ``|S|`` is found from a
  thin QR of B and a K x K eigendecomposition, in float64 on the host.
- Its eigenpairs come from the 2K x 2K Gram matrix (float64, host); the
  rows ``w_j`` of the orthonormal eigenvectors are held in float32.
- The tree over leaf blocks of ``block`` rows holds, per node, the sum of
  ``w_j w_j^T`` over its rows (float32).

Its device products run at ``highest`` precision (float32).

A served draw is judged by replaying its accepted proposal with the
served items forced (``judge``): every random decision the sampler took
on the way is re-taken from the same PRNG stream with the reference's own
probabilities, and the widest distance by which a served decision lies on
the wrong side of the reference's threshold is reported.  A proposal the
program rejected is replayed unforced (``replay``): the reference draws
it itself from the same stream and takes its own acceptance test.  The
key schedule is the one the served path documents: proposal ``t`` of a
request with seed ``s`` is keyed ``fold_in(PRNGKey(s), t)``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_TINY = 1e-30
#: proposals per compiled call of ``replay``
REPLAY_CHUNK = 2048
#: acceptance margins (nats) under which ``replay`` redoes the log ratio
#: in float64 on the host
SCREEN = 1.0
#: eigenvector coins nearest their threshold that ``judge`` also tries
#: flipped, one at a time and the nearest two together
N_AMBIGUOUS = 4


def tree_depth(m: int, block: int) -> int:
    """Levels below the root: leaf blocks are padded to a power of two."""
    n_blocks = 1 << max(0, math.ceil(math.log2(max(1, math.ceil(m / block)))))
    return n_blocks.bit_length() - 1


class Reference:
    """The reference sampler over one catalog.

    Args:
      v, b: (M, K) factors; d: (K, K).  ``L = V V^T + B (D - D^T) B^T``.
      block: rows per leaf block of the tree.
    """

    def __init__(self, v, b, d, block: int):
        v = np.asarray(v, np.float64)
        b = np.asarray(b, np.float64)
        d = np.asarray(d, np.float64)
        self.m, self.k = v.shape
        self.r = 2 * self.k
        self.block = block
        self.depth = tree_depth(self.m, block)
        a = d - d.T
        qb, rb = np.linalg.qr(b)
        c = rb @ a @ rb.T                       # skew, K x K
        ev, evec = np.linalg.eigh(c.T @ c)      # |C|^2 = C^T C
        abs_half = (evec * np.sqrt(np.sqrt(np.maximum(ev, 0.0)))) @ evec.T
        #: rows of Lhat = Zh Zh^T and of L = F diag(I, A) F^T
        self.zh = np.concatenate([v, qb @ abs_half], axis=1)
        self.f = np.concatenate([v, b], axis=1)
        self.a = a
        g = self.zh.T @ self.zh
        lam, u = np.linalg.eigh(g)
        lam = np.maximum(lam, 0.0)
        good = lam > 1e-10
        w = (self.zh @ u) / np.where(good, np.sqrt(np.maximum(lam, 1e-10)),
                                     1.0)
        w = w * good
        self.lam = lam * good
        n_pad = (1 << self.depth) * block
        w = np.pad(w, ((0, n_pad - self.m), (0, 0)))
        self.w = jnp.asarray(w, jnp.float32)
        self.levels = _build_levels(self.w, block=block, depth=self.depth)
        self.probs = jnp.asarray(self.lam / (1.0 + self.lam), jnp.float32)
        self._dev = None          # float32 rows of L and Lhat, for replay

    # ------------------------------------------------------------ exact sums
    def expected_trials(self) -> float:
        """det(Lhat + I) / det(L + I) in float64 (2K x 2K determinants)."""
        r = self.r
        cf = np.zeros((r, r))
        cf[:self.k, :self.k] = np.eye(self.k)
        cf[self.k:, self.k:] = self.a
        _, ld_l = np.linalg.slogdet(np.eye(r) + cf @ (self.f.T @ self.f))
        _, ld_h = np.linalg.slogdet(np.eye(r) + self.zh.T @ self.zh)
        return float(np.exp(ld_h - ld_l))

    def expected_size(self) -> float:
        """E|Y| of a proposal: sum of lam / (1 + lam)."""
        return float(np.sum(self.lam / (1.0 + self.lam)))

    def log_ratio(self, items: np.ndarray) -> tuple:
        """(log det L_Y - log det Lhat_Y, sign of det L_Y), float64."""
        f, zh = self.f[items], self.zh[items]
        k = self.k
        l_y = f[:, :k] @ f[:, :k].T + f[:, k:] @ self.a @ f[:, k:].T
        lh_y = zh @ zh.T
        s_l, ld_l = np.linalg.slogdet(l_y)
        _, ld_h = np.linalg.slogdet(lh_y)
        return float(ld_l - ld_h), float(s_l)

    def replay(self, seeds: Sequence[int], ts: Sequence[int]
               ) -> Dict[str, np.ndarray]:
        """The reference's own draw of proposal ``ts[i]`` of request
        ``seeds[i]``, and its acceptance test.

        Returns ``items`` (N, R), -1 padded, and ``margin`` (N,) float64:
        log u minus the log acceptance ratio in nats (below 0: the
        reference accepts).  Margins under ``SCREEN`` are computed in
        float64 on the host, the others in float32 on the device.
        """
        if self._dev is None:
            self._dev = tuple(jnp.asarray(x, jnp.float32)
                              for x in (self.f, self.zh, self.a))
        seeds = np.asarray(seeds, np.int64).astype(np.uint32)
        ts = np.asarray(ts, np.int64).astype(np.uint32)
        n = len(seeds)
        pad = -n % REPLAY_CHUNK
        seeds, ts = np.pad(seeds, (0, pad)), np.pad(ts, (0, pad))
        items, coin, margin = [], [], []
        for i in range(0, n + pad, REPLAY_CHUNK):
            its, u = _draw(self.w, self.levels, self.probs,
                           jnp.asarray(seeds[i:i + REPLAY_CHUNK]),
                           jnp.asarray(ts[i:i + REPLAY_CHUNK]),
                           block=self.block, depth=self.depth)
            lr, sign = _log_ratio32(*self._dev, its, m=self.m)
            items.append(its)
            coin.append(u)
            margin.append(jnp.where(sign > 0, jnp.log(u) - lr, jnp.inf))
        items = np.asarray(jnp.concatenate(items))[:n]
        coin = np.asarray(jnp.concatenate(coin), np.float64)[:n]
        margin = np.asarray(jnp.concatenate(margin), np.float64)[:n]
        for i in np.flatnonzero(margin < SCREEN):
            y = items[i][items[i] >= 0]
            lr, sign = self.log_ratio(y)
            margin[i] = np.log(coin[i]) - lr if sign > 0 else np.inf
        return {"items": items, "margin": margin}

    # -------------------------------------------------------------- judging
    def judge(self, seeds: Sequence[int], trials: Sequence[int],
              items: np.ndarray) -> Dict[str, np.ndarray]:
        """Per-draw gaps of served draws.

        ``seeds[i]``: request seed; ``trials[i]``: proposals it consumed
        (the accepted one is ``trials - 1``); ``items[i]``: (R,) served
        items in the order they were chosen, -1 padded.

        Returns float64 arrays over the draws:
          path_gap   — widest wrong-side distance, in probability, of an
                       eigenvector coin or a descent decision;
          leaf_gap   — widest Gumbel-max deficit of a served leaf item, in
                       nats;
          accept_gap — log u minus the reference's log acceptance ratio,
                       in nats (positive: the reference rejects);
          size       — |Y|;
          leaf_step  — the item step of the widest leaf gap.
        The eigenvector set is not part of the answer: coins within
        rounding of their threshold are tried both ways, and the reading
        of the best-fitting set is kept.
        """
        items = np.asarray(items, np.int32)
        n_draws, r = items.shape
        sizes = (items >= 0).sum(axis=1)
        seeds_u = np.asarray(seeds, np.int64).astype(np.uint32)
        t_acc = (np.asarray(trials, np.int64) - 1).astype(np.uint32)
        u_acc, u_e = _coins(jnp.asarray(seeds_u), jnp.asarray(t_acc), r)
        u_e = np.asarray(u_e, np.float64)
        probs = np.asarray(self.probs, np.float64)
        base = u_e < probs[None, :]
        near = np.argsort(np.abs(u_e - probs[None, :]), axis=1)[:, :N_AMBIGUOUS]
        flips = [()] + [(i,) for i in range(N_AMBIGUOUS)] + [(0, 1)]
        masks, coin_gap = [], []
        for fl in flips:
            msk = base.copy()
            gap = np.zeros(n_draws)
            for i in fl:
                col = near[:, i]
                rows = np.arange(n_draws)
                msk[rows, col] = ~msk[rows, col]
                gap = np.maximum(gap, np.abs(u_e[rows, col] - probs[col]))
            masks.append(msk)
            coin_gap.append(gap)
        masks = np.stack(masks, 1)                        # (D, V, R)
        coin_gap = np.stack(coin_gap, 1)                  # (D, V)
        n_var = len(flips)
        desc, leaf, leaf_step = _walk(
            self.w, self.levels,
            jnp.asarray(np.repeat(seeds_u, n_var)),
            jnp.asarray(np.repeat(t_acc, n_var)),
            jnp.asarray(masks.reshape(-1, r)),
            jnp.asarray(np.repeat(items, n_var, axis=0)),
            block=self.block, depth=self.depth)
        desc = np.asarray(desc, np.float64).reshape(n_draws, n_var)
        leaf = np.asarray(leaf, np.float64).reshape(n_draws, n_var)
        path = np.maximum(desc, coin_gap)
        path[masks.sum(axis=2) != sizes[:, None]] = np.inf
        best = np.lexsort((leaf.T, path.T), axis=0)[0]    # min path, then leaf
        pick = np.arange(n_draws)
        leaf_step = np.asarray(leaf_step).reshape(n_draws, n_var)[pick, best]
        accept_gap = np.empty(n_draws)
        u_acc = np.asarray(u_acc, np.float64)
        for i in range(n_draws):
            y = items[i][items[i] >= 0]
            bad = (len(np.unique(y)) != len(y) or np.any(y >= self.m)
                   or len(y) == 0)
            if bad:
                accept_gap[i] = np.inf
                continue
            lr, sign = self.log_ratio(y)
            accept_gap[i] = np.inf if sign <= 0 else np.log(u_acc[i]) - lr
        return {"path_gap": path[pick, best], "leaf_gap": leaf[pick, best],
                "accept_gap": accept_gap, "size": sizes.astype(np.float64),
                "leaf_step": leaf_step.astype(np.float64)}


# ------------------------------------------------------------------ kernels


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("block", "depth"))
def _build_levels(w, *, block: int, depth: int):
    """Root-first tuple of (2^l, R, R) node sums of w_j w_j^T."""
    r = w.shape[1]
    wb = w.reshape(-1, block, r)
    leaves = _mm(jnp.swapaxes(wb, 1, 2), wb)
    levels = [leaves]
    for _ in range(depth):
        cur = levels[-1]
        levels.append(cur[0::2] + cur[1::2])
    return tuple(reversed(levels))


def _streams(seed, t, r: int):
    """The served path's PRNG stream of proposal ``t`` of request ``seed``:
    (acceptance key, eigenvector-coin key, per-step keys (R, 2))."""
    key = jnp.stack([jnp.zeros_like(seed), seed]).astype(jnp.uint32)
    kt = jax.random.fold_in(key, t)
    k_prop, k_acc = jax.random.split(kt)
    k_coin, k_steps = jax.random.split(k_prop)
    return k_acc, k_coin, jax.random.split(k_steps, r)


@functools.partial(jax.jit, static_argnums=(2,))
def _coins(seeds, ts, r: int):
    def one(s, t):
        k_acc, k_coin, _ = _streams(s, t, r)
        return (jax.random.uniform(k_acc, dtype=jnp.float32),
                jax.random.uniform(k_coin, (r,), dtype=jnp.float32))
    return jax.vmap(one)(seeds, ts)


def _step(w, levels, q, key, item, *, block: int, depth: int):
    """One item step of the elementary-DPP draw.  Forced to the served
    ``item``, its decisions are re-taken with the reference's numbers;
    with ``item`` None, the reference takes them itself.  Returns (q after
    the step, widest descent gap, leaf gap, the item)."""
    kk = jax.random.split(key)
    us = jax.random.uniform(kk[0], (max(depth, 1),), dtype=jnp.float32)
    g = jax.random.gumbel(kk[1], (block,), dtype=jnp.float32)
    forced = item is not None
    blk = item // block if forced else None
    node = jnp.asarray(0, jnp.int32)
    desc_gap = jnp.asarray(-jnp.inf, jnp.float32)
    for lvl in range(1, depth + 1):
        p_par = jnp.sum(q * levels[lvl - 1][node])
        left = 2 * node
        p_left = jnp.sum(q * levels[lvl][left])
        ratio = jnp.maximum(p_left, 0.0) / jnp.maximum(p_par, _TINY)
        # the served path goes left iff u <= ratio
        if forced:
            node = blk >> (depth - lvl)
            gap = jnp.where(node == left, us[lvl - 1] - ratio,
                            ratio - us[lvl - 1])
            desc_gap = jnp.maximum(desc_gap, gap)
        else:
            node = jnp.where(us[lvl - 1] <= ratio, left, left + 1)
    if not forced:
        blk = node
    rows = jax.lax.dynamic_slice_in_dim(w, blk * block, block)
    scores = jnp.sum(_mm(rows, q) * rows, axis=1)
    logits = jnp.log(jnp.maximum(scores, 0.0) + _TINY) + g
    if forced:
        leaf_gap = jnp.max(logits) - logits[item - blk * block]
    else:
        item = blk * block + jnp.argmax(logits).astype(jnp.int32)
        leaf_gap = jnp.asarray(0.0, jnp.float32)
    wj = w[item]
    qw = _mm(q, wj[:, None])[:, 0]
    p = jnp.maximum(jnp.sum(wj * qw), _TINY)
    return q - jnp.outer(qw, qw) / p, desc_gap, leaf_gap, item


@functools.partial(jax.jit, static_argnames=("block", "depth"))
def _walk(w, levels, seeds, ts, e_masks, items, *, block: int, depth: int):
    """Teacher-forced replays: (widest descent gap, widest leaf gap, the
    item step of the widest leaf gap) per walk, over its served items."""
    r = w.shape[1]

    def one(s, t, e_mask, served):
        _, _, step_keys = _streams(s, t, r)
        n = jnp.sum(served >= 0)
        q0 = jnp.diag(e_mask.astype(w.dtype))

        def body(i, carry):
            q, dg, lg, ls = carry
            q2, d1, l1, _ = _step(w, levels, q, step_keys[i],
                                  jnp.maximum(served[i], 0), block=block,
                                  depth=depth)
            return (q2, jnp.maximum(dg, d1), jnp.maximum(lg, l1),
                    jnp.where(l1 > lg, i, ls))

        init = (q0, jnp.asarray(-jnp.inf, jnp.float32),
                jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32))
        _, dg, lg, ls = jax.lax.fori_loop(0, n, body, init)
        return dg, lg, ls

    return jax.vmap(one)(seeds, ts, e_masks, items)


@functools.partial(jax.jit, static_argnames=("block", "depth"))
def _draw(w, levels, probs, seeds, ts, *, block: int, depth: int):
    """Unforced draws: (items (N, R) -1 padded, the acceptance coin u) of
    proposal ``ts[i]`` of request ``seeds[i]``."""
    r = w.shape[1]

    def one(s, t):
        k_acc, k_coin, step_keys = _streams(s, t, r)
        e_mask = jax.random.uniform(k_coin, (r,), dtype=jnp.float32) < probs
        n = jnp.sum(e_mask)

        def body(i, carry):
            q, items = carry
            q2, _, _, item = _step(w, levels, q, step_keys[i], None,
                                   block=block, depth=depth)
            return q2, items.at[i].set(item)

        init = (jnp.diag(e_mask.astype(w.dtype)), jnp.full((r,), -1, jnp.int32))
        _, items = jax.lax.fori_loop(0, n, body, init)
        return items, jax.random.uniform(k_acc, dtype=jnp.float32)

    return jax.vmap(one)(seeds, ts)


@functools.partial(jax.jit, static_argnames=("m",))
def _log_ratio32(f, zh, a, items, *, m: int):
    """(log det L_Y - log det Lhat_Y, sign of det L_Y) in float32 for each
    row of ``items`` (-1 padded); an item out of range reads sign 0."""
    k = a.shape[0]

    def one(y):
        used = y >= 0
        idx = jnp.where(used, y, 0)
        fy = f[idx] * used[:, None]
        zy = zh[idx] * used[:, None]
        l_y = (_mm(fy[:, :k], fy[:, :k].T)
               + _mm(_mm(fy[:, k:], a), fy[:, k:].T))
        pad = jnp.diag((~used).astype(f.dtype))
        s_l, ld_l = jnp.linalg.slogdet(l_y + pad)
        _, ld_h = jnp.linalg.slogdet(_mm(zy, zy.T) + pad)
        return ld_l - ld_h, jnp.where(jnp.all(y < m), s_l, 0.0)

    return jax.vmap(one)(items)
