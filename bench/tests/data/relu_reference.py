"""Test-only plain reference: ``bench/reference.py`` with ReLU in place of
ELU between the MLPs' layers, as a configuration of another activation
would name its own reference (``"reference":
"tests/data/relu_reference.py"``). It runs that file's code in this
module's namespace, whose ``mlp`` then finds the ReLU below."""
from pathlib import Path

import jax

_SOURCE = Path(__file__).resolve().parents[2] / "reference.py"
exec(compile(_SOURCE.read_text(), str(_SOURCE), "exec"))


def _elu(x):
    return jax.nn.relu(x)
