import os
import sys

# src/ layout import path (tests also work without installing the package)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root: tests share the policy-bench probe model (benchmarks/)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest

# hypothesis is a pinned CI dep but not guaranteed in every container;
# skip the property-test modules (not the whole collection) without it
try:
    import hypothesis  # noqa: F401
    collect_ignore = []
except ImportError:
    collect_ignore = ["test_attention.py", "test_dp.py",
                      "test_fedpt_core.py", "test_kernels.py",
                      "test_optim_data.py"]


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(params=["ref", "interpret"])
def gmm_path(request, monkeypatch):
    """The grouped matmul ``ops.moe_held_ffn`` runs: the jnp ref, or the
    Pallas kernel in interpret mode, which leaves NaN in every row past
    the routed ones, so a read of such a row that is not selected away
    shows."""
    from repro.kernels import moe_gmm, ops
    if request.param == "interpret":
        monkeypatch.setattr(ops, "_grouped", lambda a, b, c, t=False:
                            moe_gmm.gmm(a, b, c, transpose_rhs=t,
                                        interpret=True))
    return request.param
