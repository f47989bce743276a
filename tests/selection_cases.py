"""Named selection patterns of the selection-kernel parity tests
(``test_kernels.py``, ``test_kernels_grad.py``)."""

import jax

# named selection patterns (see ``selection_ids``), each run on both schedules
SELECTION_CASES = ("shared", "duplicate", "invalid", "padding", "gqa4",
                   "wide-keys", "chunked")
SCHEDULES = ("resident", "blocked")


def selection_ids(case, key, shape, nb):
    """(top_idx, sel_valid) of ``shape`` (B, G, Hkv, k*) over ``nb`` blocks:
    random ids, 85% valid, shaped by ``case``:

    * ``shared`` — every group selects block nb/2 first: its dK/dV sum over
      all G groups;
    * ``duplicate`` — each group selects one block twice;
    * ``invalid`` — half the selections −1, and group 1 all invalid (its
      rows emit zeros and get zero gradient)."""
    k1, k2 = jax.random.split(key)
    idx = jax.random.randint(k1, shape, 0, nb)
    valid = jax.random.bernoulli(k2, 0.85, shape)
    if case == "shared":
        idx = idx.at[..., 0].set(nb // 2)
    elif case == "duplicate":
        idx = idx.at[..., 1].set(idx[..., 0])
        valid = valid.at[..., :2].set(True)
    elif case == "invalid":
        valid = jax.random.bernoulli(k2, 0.5, shape).at[:, 1].set(False)
    return idx, valid
