import numpy as np
import pytest


@pytest.fixture
def rng():
    """Function-scoped on purpose: the old session-scoped generator made
    every test's data depend on how many draws earlier-collected tests had
    taken, so ADDING a test file silently shifted the data of every later
    alphabetical file (and data-sensitive checks flaked).  A fresh generator
    per test keeps each test's data a pure function of its own draws."""
    return np.random.default_rng(0)
