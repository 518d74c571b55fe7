"""Each configuration file names its architecture's reference module, which
defines the reference, the program's sizes, the weights and the FLOP
count; the loader names the configuration and the module in every fault;
and the tiny cells read what they read before these moved into the
modules."""
import ast
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "chipbench"
DATA = HERE / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import reference, spec, system, traffic  # noqa: E402
from chipbench.reference.numerics import REFERENCE  # noqa: E402

CONFIG_FILES = sorted((HERE / "configs").glob("*.json")) + \
    sorted((DATA / "configs").glob("*.json"))

#: read on the parent of the move, seed 2**31 + 12345, on the CPU: model
#: FLOPs per unit, the float64 sum and sum of squares of every weight, and
#: the reference's loss on the first batch
PARITY = {
    "tiny-lm-1": (565248.0, -20.009306442341767, 1042.6096322771496,
                  5.571438789367676),
    "tiny-cnn-1": (3330816.0, 581.032896788638, 1815.4341642533989,
                   2.870742082595825),
}
SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_every_configuration_names_a_complete_module(path):
    config = json.loads(path.read_text())
    mod = reference.module_for(config)
    assert Path(mod.__file__) == \
        HERE / "reference" / f"{config['reference']}.py"
    for name in reference.FUNCTIONS:
        assert callable(getattr(mod, name)), name


def _imports_and_names(node):
    """Modules imported and names read anywhere under ``node``."""
    for n in ast.walk(node):
        if isinstance(n, ast.Import):
            yield from (a.name for a in n.names)
        elif isinstance(n, ast.ImportFrom):
            yield n.module or ""
        elif isinstance(n, ast.Name):
            yield n.id


@pytest.mark.parametrize("module", ["lm", "resnet"])
def test_the_reference_reads_nothing_of_the_program(module):
    """Only ``program_config`` may import the program; nothing else in the
    module imports it or calls ``program_config``."""
    tree = ast.parse((HERE / "reference" / f"{module}.py").read_text())
    for node in tree.body:
        if getattr(node, "name", None) == "program_config":
            continue
        for name in _imports_and_names(node):
            assert name.split(".")[0] != "repro", ast.unparse(node)[:80]
            assert name != "program_config", ast.unparse(node)[:80]


def _config(**change):
    c = json.loads((DATA / "configs" / "tiny-lm.json").read_text())
    c.update(change)
    return {k: v for k, v in c.items() if v is not None}


@pytest.mark.parametrize("config, words", [
    (_config(reference="no_such_arch"), ["tiny-lm", "no_such_arch"]),
    (_config(reference="numerics"), ["tiny-lm", "numerics", "init_leaf"]),
    (_config(reference="../lm"), ["tiny-lm", "../lm"]),
    (_config(reference=None), ["tiny-lm", "None"]),
], ids=["unknown", "incomplete", "path", "missing"])
def test_a_module_that_is_not_there_is_named(config, words):
    with pytest.raises(ValueError) as err:
        reference.module_for(config)
    for word in words:
        assert word in str(err.value)


@pytest.mark.parametrize("name", sorted(PARITY))
def test_flops_weights_and_reference_loss_are_unchanged(name):
    import jax
    flops, total, squares, loss = PARITY[name]
    cell = spec.load_cell(name, DATA)
    arch = reference.module_for(cell.config)
    assert arch.train_flops_per_unit(cell.config, cell.traffic) == flops
    used = jax.devices()[:1]
    shapes = system.param_shapes(system.build(cell, used).model)
    params = system.make_params(shapes, cell.config, SEED, used[0])
    leaves = [np.asarray(x).astype(np.float64)
              for x in jax.tree.leaves(params)]
    assert sum(float(x.sum()) for x in leaves) == \
        pytest.approx(total, rel=1e-9, abs=1e-9)
    assert sum(float(np.square(x).sum()) for x in leaves) == \
        pytest.approx(squares, rel=1e-9)
    pool = traffic.make_pool(cell.traffic, cell.config, SEED)
    ref = reference.train_readings(cell.config, cell.traffic, params,
                                   pool[:1], REFERENCE)
    assert ref["losses"][0] == pytest.approx(loss, rel=1e-6)
