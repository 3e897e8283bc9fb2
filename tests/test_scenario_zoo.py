"""Scenario-zoo acceptance tests: every registered model family runs
END-TO-END through ``run_scenario`` -> ``FederatedSession`` -> coded store ->
SE unlearning at smoke scale, with the mamba/rwkv6 paths asserted to route
through their ``ssm_scan``/``wkv`` Pallas kernel ops (interpret mode on
CPU).  Plus ``ScenarioConfig.__post_init__`` validation (typo'd registry
keys fail with actionable errors, not deep KeyErrors) and a guard that the
CI matrix smoke job covers every registered family so a registry entry can
never silently rot."""
import importlib
import re
from pathlib import Path

import numpy as np
import pytest

from repro.fl.experiment import (RequestSchedule, ScenarioConfig,
                                 UnlearnRequest, build_session,
                                 register_model_family)
from repro.fl.families import ModelFamily, canonical_families, get_model_family
from repro.fl.tasks import get_task

# kernel op -> module that owns it (what the model files import lazily)
_OP_MODULES = {"ssm_scan": "repro.kernels.ssm_scan.ops",
               "wkv": "repro.kernels.wkv.ops"}


def _family_cfg(family: str) -> ScenarioConfig:
    fam = get_model_family(family)
    schedule = RequestSchedule([UnlearnRequest(
        lambda plan: [plan.shard_clients[0][0]], framework="SE", rounds=1)])
    common = dict(model=family, store="coded", num_clients=8,
                  clients_per_round=4, num_shards=2, local_epochs=1,
                  global_rounds=2, num_stages=1, schedule=schedule)
    if fam.task == "classification":
        return ScenarioConfig(task="classification", partitioner="dirichlet",
                              partitioner_kwargs={"alpha": 1.0},
                              samples_per_client=12, image_size=8, test_n=40,
                              local_batch=2, **common)
    return ScenarioConfig(task="generation", partitioner="zipf",
                          partitioner_kwargs={"exponent": 0.5},
                          samples_per_client=6, seq_len=16, test_n=20,
                          local_batch=2, **common)


@pytest.mark.parametrize("family", canonical_families())
def test_family_end_to_end(family, monkeypatch):
    """One tiny stage + one SE request per family — the CI matrix smoke."""
    fam = get_model_family(family)
    counts = {}
    for op in fam.kernel_ops:
        mod = importlib.import_module(_OP_MODULES[op])
        real = getattr(mod, op)

        def spy(*a, _real=real, _op=op, **kw):
            counts[_op] = counts.get(_op, 0) + 1
            return _real(*a, **kw)

        monkeypatch.setattr(mod, op, spy)

    cfg = _family_cfg(family)
    session, (tx, ty) = build_session(cfg)
    report = session.run(cfg.num_stages, schedule=cfg.schedule)

    # trained + served: one stage, one SE result on the impacted shard only
    assert len(report.stages) == 1
    (res,) = report.stages[0].unlearn
    assert res.framework == "SE"
    assert list(res.impacted_shards) == [0]
    assert res.cost_units > 0
    assert report.store_stats.client_bytes > 0      # coded slices landed

    # the family's declared kernel ops were actually exercised
    for op in fam.kernel_ops:
        assert counts.get(op, 0) > 0, f"{family} never routed through {op!r}"

    # task-appropriate eval metrics, finite
    metrics = session.sim.evaluate(res.models, tx, ty)
    assert all(np.isfinite(v) for v in metrics.values()), metrics
    if fam.task == "generation":
        assert "ppl" in metrics and "bpc" in metrics
        assert metrics["ppl"] == pytest.approx(np.exp(metrics["loss"]),
                                               rel=1e-6)


class TestFamilyRegistry:
    def test_kernel_declarations(self):
        assert get_model_family("mamba").kernel_ops == ("ssm_scan",)
        assert get_model_family("rwkv6").kernel_ops == ("wkv",)
        assert get_model_family("mamba").build(
            _family_cfg("mamba")).mamba_impl == "pallas"
        assert get_model_family("rwkv6").build(
            _family_cfg("rwkv6")).rwkv_impl == "pallas"

    def test_aliases_resolve_to_same_class(self):
        assert type(get_model_family("rwkv")) is type(get_model_family("rwkv6"))
        assert type(get_model_family("nanogpt")) is type(
            get_model_family("transformer"))

    def test_third_party_family_is_one_class(self):
        from repro.fl.experiment import build_simulator

        @register_model_family("cnn-wide-test")
        class WideCNN(ModelFamily):
            task = "classification"

            def build(self, cfg):
                import dataclasses
                from repro.configs import get_config
                return dataclasses.replace(get_config("cnn-paper"),
                                           image_size=cfg.image_size,
                                           d_model=64, cnn_channels=(4, 8))

        try:
            cfg = ScenarioConfig(model="cnn-wide-test", num_clients=4,
                                 clients_per_round=4, num_shards=2,
                                 samples_per_client=8, image_size=8)
            sim, _test = build_simulator(cfg)
            assert sim.cfg.d_model == 64
        finally:
            from repro.fl.families import FAMILIES
            FAMILIES.pop("cnn-wide-test", None)

    def test_default_families_per_task(self):
        assert get_task("classification").default_family == "cnn"
        assert get_task("generation").default_family == "transformer"


class TestScenarioValidation:
    def test_unknown_task_lists_registered(self):
        with pytest.raises(ValueError, match="registered:.*classification"):
            ScenarioConfig(task="vision")

    def test_unknown_model_lists_registered(self):
        with pytest.raises(ValueError, match="registered:.*mamba"):
            ScenarioConfig(model="mambo")

    def test_unknown_partitioner_lists_registered(self):
        with pytest.raises(ValueError, match="registered:.*zipf"):
            ScenarioConfig(partitioner="zpif")

    def test_typod_partitioner_kwarg_fails_at_config_time(self):
        with pytest.raises(ValueError, match="accepted:.*alpha"):
            ScenarioConfig(partitioner="dirichlet",
                           partitioner_kwargs={"alhpa": 0.1})

    def test_unknown_store_lists_registered(self):
        with pytest.raises(ValueError, match="registered:.*coded"):
            ScenarioConfig(store="codedx")

    def test_unknown_scheduled_framework(self):
        sched = RequestSchedule([UnlearnRequest([0], framework="SEE")])
        with pytest.raises(ValueError, match="registered:.*SE"):
            ScenarioConfig(schedule=sched)

    def test_model_task_mismatch(self):
        with pytest.raises(ValueError, match="plays task"):
            ScenarioConfig(task="classification", model="rwkv6")

    def test_shards_must_divide_sampled_clients(self):
        with pytest.raises(ValueError, match="must divide"):
            ScenarioConfig(clients_per_round=10, num_shards=4)

    def test_clients_per_round_bounded(self):
        with pytest.raises(ValueError, match="exceeds num_clients"):
            ScenarioConfig(num_clients=4, clients_per_round=8)

    def test_bad_slice_dtype(self):
        with pytest.raises(ValueError, match="bfloat16"):
            ScenarioConfig(slice_dtype="floatiest")
        ScenarioConfig(slice_dtype="bfloat16")       # jnp extension dtype OK
        ScenarioConfig(slice_dtype=np.float16)

    def test_iid_and_partitioner_conflict(self):
        with pytest.warns(DeprecationWarning):
            with pytest.raises(ValueError, match="not both"):
                ScenarioConfig(iid=True, partitioner="zipf")


def test_ci_matrix_covers_all_registered_families():
    """The CI scenario-zoo matrix must name every registered family — adding
    a family without smoke coverage fails here, not in production."""
    ci = (Path(__file__).resolve().parents[1] / ".github" / "workflows"
          / "ci.yml").read_text()
    m = re.search(r"family:\s*\[([^\]]*)\]", ci)
    assert m, "ci.yml has no scenario-zoo family matrix"
    listed = {s.strip() for s in m.group(1).split(",") if s.strip()}
    assert listed == set(canonical_families()), (
        f"CI matrix {sorted(listed)} != registered families "
        f"{sorted(canonical_families())}")
