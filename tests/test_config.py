"""Config files written before an option was removed still load.

`Config.from_json` ignores keys it does not know, so a `node.json` that
still carries a removed `decision` key (docs/Migration.md) loads, and the
node it configures solves on the one device kernel family there is."""

import asyncio
import dataclasses
import inspect
import json

import pytest

from openr_tpu.config import Config
from openr_tpu.config.config import DecisionConfig
from openr_tpu.decision import Decision
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters
from openr_tpu.utils import topogen
from tests.test_decision import adj_pub, next_update, prefix_pub


@pytest.mark.parametrize(
    "key,value",
    [
        ("use_dense_kernel", True),
        ("use_pallas_kernel", True),
        ("spf_kernel", "dense"),
    ],
)
def test_node_json_with_removed_engine_key_loads_and_solves_split(key, value):
    cfg = Config.from_json(json.dumps({
        "node_name": "node-0",
        "decision": {
            key: value,
            "native_rib": "off",
            "debounce_min_ms": 5,
            "debounce_max_ms": 20,
        },
    }))
    assert not hasattr(cfg.node.decision, key)
    assert key not in json.loads(cfg.to_json())["decision"]

    async def body():
        pubs = ReplicateQueue(name="pubs")
        routes = ReplicateQueue(name="routes")
        reader = routes.get_reader()
        d = Decision(cfg, pubs.get_reader(), routes, counters=Counters())
        await d.start()
        adj_dbs, prefix_dbs = topogen.fat_tree(4)
        pubs.push(adj_pub(adj_dbs))
        pubs.push(prefix_pub(prefix_dbs))
        await next_update(reader, timeout=60.0)
        await d.stop()
        return d

    d = asyncio.run(body())
    solver = d._tpu
    assert solver.spf_kernel_stats["engine_device"] >= 1
    assert solver.spf_kernel_stats["engine_native"] == 0
    assert [set(c["sets"]) for c in solver._dev.values()] == [{"split"}]


def test_engine_choice_is_two_config_fields_and_one_parameter():
    """What chooses an SPF engine: whether the device solves at all and
    which engine owns one root (ROADMAP S7). A field or a parameter
    added beside them is a new duplicate path and needs a cell on each
    side of it first."""
    fields = {f.name: f.default for f in dataclasses.fields(DecisionConfig)}
    assert {k for k in fields if "kernel" in k or "solver" in k} == {
        "use_tpu_solver"
    }
    assert fields["use_tpu_solver"] is True
    assert fields["native_rib"] == "auto"
    params = inspect.signature(TpuSpfSolver.__init__).parameters
    assert list(params) == [
        "self", "enable_lfa", "ksp_k", "native_rib", "mesh", "counters",
    ]
    assert params["native_rib"].default == "auto"
