"""Plain reference of one federated round over OMC storage (paper section 2).

Server weights are the dequantized storage.  Each client in turn:
quantize-dequantizes the variables its partial-quantization mask picks (one
affine per whole variable), takes ``local_steps`` SGD steps on its batches,
quantize-dequantizes its result under the same mask, and sends it quantized
once more for transport (one affine per stacked layer).  The server averages
the uploads of the clients that report, moves ``server_lr`` of the way to the
mean and stores the result quantized (one affine per stacked layer).
Unselected variables (norms, biases) stay float32 throughout.

One client at a time, in ``dtype`` (float32 for the reference, a lower one
for the control), with matmuls at ``precision``: the configuration's.
Imports nothing of the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import omc


def paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(k.key) for k in p) for p, _ in flat]


def stack_axes(tree, layout):
    """``{path: number of stacked axes}`` in tree order."""
    return {p: int(bool(layout[p][2])) for p in paths(tree)}


def selection(tree, layout):
    """Paths the weights-only rule picks, in tree order (the mask order)."""
    stacks = stack_axes(tree, layout)
    flat = jax.tree_util.tree_leaves(tree)
    return [p for p, leaf in zip(paths(tree), flat)
            if omc.selected(p, leaf.shape, stacks[p])]


def _map(fn, tree, *rest):
    """``fn(path, leaf, *other leaves)`` over the tree in tree order."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    others = [jax.tree_util.tree_leaves(r) for r in rest]
    out = [fn(p, leaf, *(o[i] for o in others))
           for i, (p, leaf) in enumerate(zip(paths(tree), leaves))]
    return jax.tree_util.tree_unflatten(treedef, out)


def store(tree, layout, fmt):
    """Server storage as its dequantized values: one affine per layer."""
    stacks = stack_axes(tree, layout)
    sel = set(selection(tree, layout))
    return _map(lambda p, v: omc.qdq(v, fmt, stacks[p]) if p in sel else v, tree)


class Round:
    """The round of a cell, built once; ``__call__`` runs one round."""

    def __init__(self, loss_fn, layout, tree_like, *, fmt, fraction, ppq_seed,
                 local_steps, client_lr, server_lr, data_fn, dtype=jnp.float32,
                 precision="highest"):
        self.layout, self.fmt, self.fraction = layout, fmt, fraction
        self.ppq_seed, self.server_lr = ppq_seed, server_lr
        self.stacks = stack_axes(tree_like, layout)
        self.sel = selection(tree_like, layout)
        index = {p: i for i, p in enumerate(self.sel)}
        steps = jnp.arange(local_steps)

        def view(tree, mask):
            def f(p, v):
                i = index.get(p)
                return v if i is None else jnp.where(mask[i], omc.qdq(v, fmt), v)
            return _map(f, tree)

        def client(server, mask, client_id, round_index):
            with jax.default_matmul_precision(precision):
                batches = jax.vmap(lambda s: data_fn(client_id, round_index, s))(steps)
                eff = jax.tree_util.tree_map(lambda v: v.astype(dtype),
                                             view(server, mask))

                def step(p, batch):
                    loss, g = jax.value_and_grad(loss_fn)(p, batch)
                    return jax.tree_util.tree_map(
                        lambda a, b: (a - client_lr * b).astype(dtype), p, g), loss

                trained, losses = jax.lax.scan(step, eff, batches)
                up = view(jax.tree_util.tree_map(
                    lambda v: v.astype(jnp.float32), trained), mask)
                sent = _map(lambda p, v: omc.qdq(v, fmt, self.stacks[p])
                            if p in index else v, up)
                return sent, losses.astype(jnp.float32).mean()

        def server_step(old, total, count):
            def f(p, o, t):
                new = o + server_lr * (t / count - o)
                return omc.qdq(new, fmt, self.stacks[p]) if p in index else new
            return _map(f, old, total)

        self._client = jax.jit(client)
        self._server = jax.jit(server_step)

    def __call__(self, server, ids, round_index):
        """``(new server, mean client loss)`` of the clients ``ids``."""
        total, losses = None, []
        for c in np.asarray(ids).tolist():
            mask = omc.ppq_mask(self.ppq_seed, round_index, c, len(self.sel),
                                self.fraction)
            sent, loss = self._client(server, mask, jnp.int32(c), jnp.int32(round_index))
            total = sent if total is None else jax.tree_util.tree_map(jnp.add, total, sent)
            losses.append(float(loss))
        return self._server(server, total, jnp.float32(len(losses))), float(np.mean(losses))


def leaf_norms(tree):
    return np.array([float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
                     for v in jax.tree_util.tree_leaves(tree)])


def norm_gaps(prog_change, ref_change, keep):
    """Per kept leaf, ``|‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)``."""
    p, r = leaf_norms(prog_change)[keep], leaf_norms(ref_change)[keep]
    return np.abs(p - r) / np.maximum(r, np.median(r))


def diff_norms(prog_change, ref_change, keep):
    """Per kept leaf, ``‖prog - ref‖ / max(‖ref‖, median leaf ‖ref‖)``."""
    d = np.array([float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()))
                  for a, b in zip(jax.tree_util.tree_leaves(prog_change),
                                  jax.tree_util.tree_leaves(ref_change))])[keep]
    r = leaf_norms(ref_change)[keep]
    return d / np.maximum(r, np.median(r))


def moved(ref_first_update, rel: float = 1e-3):
    """Leaves the reference moves: update norm at least ``rel`` of the
    median leaf's.  The others move by round-off alone."""
    r = leaf_norms(ref_first_update)
    return r >= rel * np.median(r)


@jax.jit
def diff(a, b):
    return jax.tree_util.tree_map(jnp.subtract, a, b)
