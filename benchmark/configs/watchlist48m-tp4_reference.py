"""Plain reference of the ``watchlist48m-tp4`` configuration.

The nets are ``watchlist8m``'s (the same committed files), so gate,
detector, decode, crop, standardization and embedder are
``watchlist8m_reference.py``'s, taken from that file as it stands: float32
``jax.numpy`` at ``highest`` matmul precision, nothing imported from the
program.

What differs is the watchlist: 50,331,648 rows that no one device holds.
The reference is handed them sharded over the chips (drawn again from the
seed once the program is gone, ``stacks/recognize_sharded.reference_rows``)
and never puts them together: ``match`` walks the shards and, within each,
blocks of ``block_rows``, every block a float32 dot at ``highest`` on the
chip that holds it (the chips work through their own blocks side by side),
the running best kept on the host, taken in row order.
No kernel, no ``shard_map``, no collective, no candidate list: the best row
of the whole watchlist is the best of the blocks' bests, ties to the lowest
row. ``sims_at`` gathers each named row from the shard that holds it.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "watchlist8m_reference",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "watchlist8m_reference.py"))
nets_reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(nets_reference)

HIGHEST = nets_reference.HIGHEST
as_stored = nets_reference.as_stored
int8_rows = nets_reference.int8_rows


def shards_of(rows):
    """[(first row, that shard's rows on its own device)] in row order,
    one entry a distinct range of rows (replicas of a shard counted once);
    an array on one device, or on the host, is one shard."""
    if not isinstance(rows, jax.Array):
        rows = jnp.asarray(rows)
    found = {}
    for shard in rows.addressable_shards:
        found.setdefault(int(shard.index[0].start or 0), shard.data)
    return sorted(found.items())


class Reference(nets_reference.Reference):
    """``watchlist8m``'s nets; the match over a watchlist in shards."""

    def match(self, queries: np.ndarray, rows, skip_head: int,
              head_rows: np.ndarray, block_rows: int):
        """Cosine top-1 of [Q, E] queries over ``head_rows`` (the enrolled
        rows, float32, standing for gallery rows 0..skip_head) and
        ``rows[skip_head:]`` (bf16, sharded by rows over the chips).
        Returns (best sims [Q], best row index [Q]) and a function giving
        the sims at named rows."""
        q_host = np.asarray(queries, np.float32)
        lower = self.lower_gallery
        q = jnp.asarray(q_host)
        if lower:
            q = int8_rows(q)
        q_host = np.asarray(q)

        @jax.jit
        def block_best(q, shard, start, first):
            g = jax.lax.dynamic_slice_in_dim(shard, start, block_rows, 0)
            g = g.astype(jnp.float32)
            if lower:
                g = int8_rows(g)
            s = jnp.dot(q, g.T, precision=HIGHEST)
            idx = first + start + jnp.arange(block_rows)
            s = jnp.where(idx[None, :] >= skip_head, s, -jnp.inf)
            return jnp.max(s, axis=1), first + start + jnp.argmax(s, axis=1)

        @jax.jit
        def rows_dot(q, shard, local):
            g = jnp.take(shard, local, axis=0).astype(jnp.float32)
            if lower:
                g = int8_rows(g)
            return jnp.sum(q * g, axis=-1)

        best = np.full((len(q_host),), -np.inf, np.float32)
        best_idx = np.full((len(q_host),), -1, np.int64)
        if len(head_rows):
            head = as_stored(head_rows)  # kept in bf16 like every row
            if lower:
                head = int8_rows(head)
            s = np.asarray(jnp.dot(q, head.T, precision=HIGHEST))
            best, best_idx = s.max(axis=1), s.argmax(axis=1).astype(np.int64)
        shards = shards_of(rows)
        blocks = []  # every block's best, queued on the chip that holds it
        for first, shard in shards:
            if shard.shape[0] % block_rows:
                raise ValueError(f"a shard of {shard.shape[0]} rows is not a "
                                 f"whole number of blocks of {block_rows}")
            q_there = jax.device_put(q_host, shard.device)
            for start in range(0, shard.shape[0], block_rows):
                if first + start + block_rows > skip_head:
                    blocks.append(block_best(q_there, shard, start, first))
        for vals, idx in blocks:  # in row order; the chips work side by side
            vals, idx = np.asarray(vals), np.asarray(idx)
            better = vals > best  # strictly: a tie stays at the lower row
            best = np.where(better, vals, best)
            best_idx = np.where(better, idx, best_idx)

        def sims_at(row_index: np.ndarray) -> np.ndarray:
            """Sim of query i with gallery row ``row_index[i]`` (>= skip_head)."""
            row_index = np.asarray(row_index, np.int64)
            out = np.zeros((len(q_host),), np.float32)
            for first, shard in shards:
                here = (row_index >= first) & (row_index < first + shard.shape[0])
                if here.any():
                    local = np.where(here, row_index - first, 0).astype(np.int32)
                    got = np.asarray(rows_dot(
                        jax.device_put(q_host, shard.device), shard, local))
                    out[here] = got[here]
            return out

        return best, best_idx, sims_at
