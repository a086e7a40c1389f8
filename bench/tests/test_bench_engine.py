"""The engine a cell runs is set by its configuration file alone: the
settings under `served.engine`, LCD codes or dense weights, and the mesh
over the cell's chips."""
import copy
import dataclasses

import jax
import pytest

from bench import run as R
from bench.tests import tiny
from repro.core.api import is_clustered


def _cell(engine, lcd=True, chips=1):
    cell = tiny.cell()
    spec = copy.deepcopy(cell.spec)
    spec["served"]["engine"] = engine
    if not lcd:
        spec.pop("lcd")
    return dataclasses.replace(cell, spec=spec, chips=chips)


@pytest.mark.parametrize("engine,lcd", [
    ({"kv_dtype": "int8"}, True),
    ({"kv_dtype": "float", "prefix_cache": True}, True),
    ({"kv_dtype": "float"}, False),
])
def test_engine_settings_come_from_the_configuration(engine, lcd, tmp_path):
    cell = _cell(engine, lcd)
    eng, params, _ = R.build(cell, 5, require_kernels=False)
    for k, v in engine.items():
        assert getattr(eng.ecfg, k) == v
    leaves = jax.tree_util.tree_leaves(params, is_leaf=is_clustered)
    assert any(is_clustered(x) for x in leaves) is lcd
    del eng
    res = R.run_cell(cell, 2**33 + 1, 1.0, False, require_chip=False,
                     out_dir=tmp_path)
    assert res["correct"], res["check"]


def test_cell_on_several_chips_shards_the_model():
    ecfg = R.engine_config(_cell({"kv_dtype": "float"}, chips=4),
                           "qwen2-1.5b")
    assert ecfg.model_parallel == 4 and ecfg.data_parallel is None
    ecfg = R.engine_config(_cell({"data_parallel": 4}, chips=4),
                           "qwen2-1.5b")
    assert ecfg.model_parallel is None and ecfg.data_parallel == 4
    mesh = R.mesh_of(R.engine_config(_cell({}), "qwen2-1.5b"),
                     jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}
