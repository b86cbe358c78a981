"""Targeted learning (paper §1, Figs. 7/10): augment training data with
unlabeled-pool samples that match a *target* distribution using FLQMI —
the query-only-kernel MI measure that needs just a (|Q| x |V|) kernel.

Scenario: the model underperforms on two rare modes; we have a small query
set from those modes and a large unlabeled pool (a seeded mixture of 8
modes). FLQMI picks pool items matching the target; we check the precision
of the retrieval across the eta trade-off, and that the sharded route (a
unit ``batch x data`` mesh) returns the sequential answer bit for bit.

    PYTHONPATH=src python examples/targeted_selection.py
"""
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import FLQMI, SelectionSpec, create_kernel, solve  # noqa: E402

N_MODES = 8


def mixture(rng, centres, n):
    """``n`` points around the seeded mode ``centres``, and their modes."""
    modes = rng.integers(0, len(centres), n)
    x = centres[modes] + rng.normal(size=(n, centres.shape[1]))
    return x.astype(np.float32), modes


def main():
    rng = np.random.default_rng(0)
    centres = rng.normal(scale=4.0, size=(N_MODES, 16))

    # unlabeled pool: 128 examples across all 8 modes
    pool, pool_modes = mixture(rng, centres, 128)

    # target: rare modes 2 and 5 (say the model underperforms there);
    # queries are held-out examples of those modes, not pool members
    rare = [2, 5]
    held_out, held_modes = mixture(rng, centres, 200)
    queries = np.concatenate([held_out[held_modes == m][:3] for m in rare])

    S_qv = create_kernel(queries, pool, metric="euclidean")
    base_rate = np.isin(pool_modes, rare).mean()
    budget = 16
    for eta in (0.0, 1.0, 4.0):
        res = solve(SelectionSpec(FLQMI.build(S_qv, eta=eta), budget))
        chosen = np.asarray(res.order)
        hits = int(np.isin(pool_modes[chosen[chosen >= 0]], rare).sum())
        print(f"eta={eta:4.1f}: {hits}/{budget} selected items are target-mode "
              f"(pool base rate {base_rate:.0%})")
        if eta > 0:  # eta weighs the pool's match to Q; at 0 only Q's coverage counts
            assert hits / budget > base_rate, (eta, hits)

    # the same request on the sharded route: one (batch x data) mesh, here a
    # unit one; bigger meshes shard the pool's columns over "data"
    spec = SelectionSpec(FLQMI.build(S_qv, eta=1.0), budget)
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    (sharded,) = solve([spec], mesh=mesh)
    sequential = solve(spec)
    assert sharded.as_list() == sequential.as_list(), "sharded != sequential"
    top = [i for i, _ in sharded.as_list()]
    hits = int(np.isin(pool_modes[top], rare).sum())
    print(f"sharded FLQMI: {hits}/{budget} target-mode, bit-identical to "
          f"sequential (first picks {top[:5]})")
    assert hits >= budget // 2, hits


if __name__ == "__main__":
    main()
