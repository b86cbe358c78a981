"""CRAIG per-class coresets (Mirzasoleiman et al., ICML 2020) — the paper's
"efficient training" application, as one served wave.

Once per epoch a training run picks, for every class, the medoids of the
class's last-layer gradients: facility location over the euclidean
similarity of the gradients, one greedy per class.  Each medoid is weighted
by the number of class points it represents, and the run trains on the
weighted coreset.  Here the gradients are seeded: softmax outputs of a
mixture of modes minus the class's one-hot label.

All classes travel as one wave through ``SelectionServer`` (``use_kernel``
left to the program's own backend choice), and every answer is checked
against a sequential ``solve``.

    PYTHONPATH=src python examples/coreset_training.py [--classes 10 --n 400]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    FacilityLocation,
    SelectionSpec,
    create_kernel,
    solve,
)
from repro.launch.serve import SelectionServer  # noqa: E402


def class_gradients(rng, classes, n, modes=8, scale=1.0):
    """(classes, n, classes) last-layer gradients: softmax(logits) - onehot,
    the logits drawn around ``modes`` seeded centres per class."""
    grads = []
    for c in range(classes):
        centres = rng.normal(size=(modes, classes))
        centres[:, c] += 3.0  # the class's own logit dominates
        which = rng.integers(0, modes, n)
        logits = scale * (centres[which] + 0.3 * rng.normal(size=(n, classes)))
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        grads.append(probs - np.eye(classes)[c])
    return np.stack(grads).astype(np.float32)


def medoid_weights(sim, picks):
    """CRAIG's weights: how many class points each medoid is closest to."""
    owner = np.argmax(np.asarray(sim)[:, picks], axis=1)
    return np.bincount(owner, minlength=len(picks))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--n", type=int, default=400, help="points per class")
    ap.add_argument("--fraction", type=float, default=0.1)
    a = ap.parse_args()

    rng = np.random.default_rng(0)
    grads = class_gradients(rng, a.classes, a.n)
    budget = max(int(a.fraction * a.n), 1)
    fns = [
        FacilityLocation.from_kernel(
            create_kernel(g, metric="euclidean"), use_kernel=None
        )
        for g in grads
    ]
    specs = [SelectionSpec(fn, budget) for fn in fns]

    server = SelectionServer()
    responses = server.select(specs)
    print(f"{a.classes} classes x {a.n} points, budget {budget} per class, "
          f"served in {server.stats.summary()['waves']} wave(s)")

    for c, (spec, resp) in enumerate(zip(specs, responses)):
        assert resp.selection == solve(spec).as_list(), f"class {c}: served != sequential"
        picks = np.asarray([i for i, _ in resp.selection])
        weights = medoid_weights(spec.fn.sim, picks)
        assert weights.sum() == a.n, "every point is represented by one medoid"

        coreset_value = float(spec.fn.evaluate_indices(picks))
        random_value = float(
            spec.fn.evaluate_indices(rng.choice(a.n, size=len(picks), replace=False))
        )
        assert coreset_value > random_value, (c, coreset_value, random_value)
        print(f"class {c}: {len(picks)} medoids, weights {weights.min()}-"
              f"{weights.max()}, FL value {coreset_value:.2f} "
              f"(random subset {random_value:.2f})")
    print("every class coreset beats a random subset of the same size")


if __name__ == "__main__":
    main()
