"""Weights from ``--seed``, drawn on the device in the type they are served in.

A reference module (``reference/<model_type>.py``) lists the model's tensors
by their published names (``GLOBAL`` and ``LAYER`` tables) in KERNEL form: a
projection is stored ``[in, out]``, the transpose of torch's ``weight``, so
that neither side moves data.  The program's parameter tree is made from
them in ONE jitted call (``program_params``); the reference draws the same
tensors again, one layer at a time, from the same keys (``draw_layer``), and
never sees an array the program holds.

The generator is XLA's ``RngBitGenerator`` (``impl="rbg"``): the bits depend
on the key and the shape alone, so both sides get the same values.

Kinds of tensor: ``matrix`` and ``bias`` (normal, the configuration's
``init_std``), ``gain`` (1 + 0.1 normal), and ``key_bias`` — the key
projection's bias, shape ``[kv heads, head size]``: a ``bias`` whose first
channel of every head is raised by ``KEY_OUTLIER``.  Why: the keys of
trained models have a few channels of far larger magnitude than the rest
(what KIVI and KVQuant are built around), and random normal weights have
none.  A constant added to one key channel adds the same amount to every
score of a query, so the exact attention does not change at all, and bf16,
a floating type, keeps its relative precision on the other channels; an
int8 cache scaled by each row's largest value spends its 255 levels on the
outlier.  Without it ``correct`` cannot tell an int8 KV cache from the bf16
one the configurations state (PERF.md section 2).
"""

import jax
import jax.numpy as jnp

GLOBAL_ID = 1 << 20  # fold-in id of the tensors that belong to no layer
KEY_OUTLIER = 32.0  # ~25 standard deviations of a key channel at init_std 0.02


def base_key(seed):
    """A key for any whole ``--seed`` (they exceed 31 bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(key, shape, kind, std, dtype):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind in ("matrix", "bias"):
        return (std * n).astype(dtype)
    if kind == "key_bias":
        return (std * n).at[..., 0].add(KEY_OUTLIER).astype(dtype)
    if kind == "gain":
        return (1.0 + 0.1 * n).astype(dtype)
    raise ValueError(kind)


def draw_table(key, owner_id, table, hf, dtype):
    """``{name: array}`` for one table of ``(name, shape_fn, kind)`` rows.
    ``owner_id``: the layer index (may be traced) or ``GLOBAL_ID``."""
    okey = jax.random.fold_in(key, owner_id)
    std = float(hf.get("init_std", hf.get("initializer_range", 0.02)))
    return {name: _draw(jax.random.fold_in(okey, j), shape_fn(hf), kind, std,
                        dtype)
            for j, (name, shape_fn, kind) in enumerate(table)}


def program_params(ref, hf, key, like, dtype):
    """The program's parameter tree, made in one jitted call.  ``like`` is a
    tree of ``ShapeDtypeStruct`` (with shardings) of the tree the program
    built for itself: the result must match it leaf for leaf."""
    n_layers = ref.num_layers(hf)

    def make(key):
        g = draw_table(key, GLOBAL_ID, ref.GLOBAL, hf, dtype)
        layers = [draw_table(key, i, ref.LAYER, hf, dtype)
                  for i in range(n_layers)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(make, key)
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), like)
    got = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), shapes)
    if want != got:
        diff = [f"{k}: program {want.get(k)} benchmark {got.get(k)}"
                for k in sorted(set(want) | set(got))
                if want.get(k) != got.get(k)]
        raise SystemExit("seeded weights do not match the program's "
                         "parameter tree:\n  " + "\n  ".join(diff[:8]))
    shardings = jax.tree.map(lambda s: s.sharding, like)
    if all(isinstance(s, jax.sharding.SingleDeviceSharding)
           for s in jax.tree.leaves(shardings)):
        # as the library leaves its own on one chip: not committed to a
        # device.  A committed tree would commit every program's outputs,
        # and each program would then compile once more for them.
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=shardings)(key)
