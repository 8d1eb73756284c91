"""Fleet-serving semantics: a single-tenant fleet is bit-for-bit the
existing single-pipeline runtime, priority classes shed in order under
overload, ``FleetSpec`` round-trips through JSON and the registry, and the
fleet-level arbitration actually reallocates cluster shares."""

import json

import pytest

from repro import api
from repro.core.mdp import ADAPTATION_INTERVAL
from repro.serving.fleet import build_fleet, scale_topology


def _json_roundtrip(d: dict) -> dict:
    return json.loads(json.dumps(d))


def _fleet_spec(**overrides):
    spec = api.get_fleet("fleet-3tenant-hetero")
    return api.replace(spec, **overrides) if overrides else spec


def _single_tenant_spec(horizon=60):
    base = _fleet_spec()
    tenant = api.TenantSpec(
        name="solo",
        pipeline=api.get_pipeline("serve2"),
        scenario=api.replace(api.get_scenario("bursty"), seed=3, horizon=horizon),
        controller=api.get_controller("greedy"),
    )
    return api.replace(
        base, name="fleet-solo", tenants=(tenant,), admission_limit=None
    )


class TestFleetSpecs:
    def test_json_roundtrip(self):
        spec = _fleet_spec()
        back = api.FleetSpec.from_dict(_json_roundtrip(spec.to_dict()))
        assert back == spec

    def test_registry(self):
        assert "fleet-3tenant-hetero" in api.list_fleets()
        spec = api.get_fleet("fleet-3tenant-hetero")
        assert len(spec.tenants) == 3
        assert spec.cluster.name == "edge-hetero-3"
        with pytest.raises(KeyError):
            api.get_fleet("no-such-fleet")
        mine = api.register_fleet(api.replace(spec, name="custom-fleet"))
        assert api.get_fleet("custom-fleet") == mine

    def test_tenant_pipeline_rebinds_cluster(self):
        spec = _fleet_spec()
        for t in spec.tenants:
            assert spec.tenant_pipeline(t).cluster == spec.cluster


class TestSingleTenantDegenerate:
    """A fleet of one tenant must reproduce the standalone runtime exactly:
    same rewards, same telemetry summary, event for event."""

    def test_bit_for_bit_vs_serving_runtime(self):
        fleet_spec = _single_tenant_spec(horizon=60)
        tenant = fleet_spec.tenants[0]
        exp = api.ExperimentSpec(
            pipeline=fleet_spec.tenant_pipeline(tenant),
            scenario=tenant.scenario,
            controller=tenant.controller,
            seq_len=fleet_spec.seq_len,
        )

        solo = api.Session.from_spec(exp)
        solo_rep = solo.serve()

        sess = api.FleetSession.from_spec(fleet_spec)
        fleet_rep = sess.serve()

        assert fleet_rep["rewards"]["solo"] == solo_rep["rewards"]
        ft = fleet_rep["summary"]["tenants"]["solo"]
        st = solo_rep["summary"]
        for key in (
            "served",
            "arrived",
            "shed",
            "shed_rate",
            "throughput_rps",
            "latency_mean_s",
            "p50",
            "p95",
            "p99",
            "mean_batch_size",
            "reconfigs",
            "migrations",
        ):
            assert ft[key] == st[key], key
        # the single tenant always owns the whole cluster: share exactly 1.0
        # and the topology object was never swapped out
        assert ft["share"] == 1.0
        assert sess.fleet.reallocations == 0

    def test_shed_zero_without_admission_limit(self):
        rep = api.FleetSession.from_spec(_single_tenant_spec()).serve()
        t = rep["summary"]["tenants"]["solo"]
        assert t["shed"] == 0
        assert t["arrived"] == t["served"]


class TestScaleTopology:
    def test_identity_at_full_share(self):
        topo = api.get_cluster("edge-hetero-3").build()
        assert scale_topology(topo, 1.0) is topo

    def test_scales_every_node(self):
        topo = api.get_cluster("edge-hetero-3").build()
        half = scale_topology(topo, 0.5)
        assert half.hop_latency == topo.hop_latency
        for node, base in zip(half.nodes, topo.nodes, strict=True):
            assert node.capacity == base.capacity * 0.5
            assert node.speed == base.speed


class TestPriorityShedding:
    def _overloaded(self, horizon=40):
        """The built-in fleet with every tenant's rate cranked far beyond
        the cluster's capacity and a tight admission limit."""
        spec = _fleet_spec()
        tenants = tuple(
            api.replace(
                t,
                scenario=api.replace(t.scenario, rate=120.0, horizon=horizon),
            )
            for t in spec.tenants
        )
        return api.replace(spec, tenants=tenants, admission_limit=150.0)

    def test_low_priority_sheds_first(self):
        rep = api.FleetSession.from_spec(self._overloaded()).serve()
        t = rep["summary"]["tenants"]
        by_prio = sorted(t.values(), key=lambda s: s["priority"])
        rates = [s["shed_rate"] for s in by_prio]
        # overload is real: somebody shed
        assert rep["summary"]["fleet"]["shed"] > 0
        # shed rate is monotone non-increasing in priority, and the lowest
        # class strictly bears more than the highest
        assert rates[0] >= rates[1] >= rates[2]
        assert rates[0] > rates[-1]

    def test_high_priority_latency_protected(self):
        rep = api.FleetSession.from_spec(self._overloaded()).serve()
        t = rep["summary"]["tenants"]
        assert t["interactive"]["p99"] <= t["batch"]["p99"]

    def test_offered_equals_served_plus_shed(self):
        rep = api.FleetSession.from_spec(self._overloaded()).serve()
        f = rep["summary"]["fleet"]
        assert f["offered"] == f["served"] + f["shed"]
        for s in rep["summary"]["tenants"].values():
            assert s["arrived"] == s["served"] + s["shed"]


class TestFleetReallocation:
    def test_shares_track_priority_and_load(self):
        sess = api.FleetSession.from_spec(_fleet_spec())
        sess.serve(horizon=40)
        fleet = sess.fleet
        assert fleet.reallocations >= 1
        shares = [t.share for t in fleet.tenants]
        assert all(s >= 0.05 for s in shares)  # min_share floor held
        assert sum(shares) <= 1.0 + 1e-9  # never oversubscribed
        # every tenant's controller/env/runtime sees its scaled view
        for t in fleet.tenants:
            if t.share < 1.0:
                total = sum(n.capacity for n in t.env.pipe.topo.nodes)
                base = sum(n.capacity for n in t._base_pipe.topo.nodes)
                assert total == pytest.approx(base * t.share)
                assert t.controller.pipe is t.env.pipe
                assert t.env.runtime.pipe is t.env.pipe

    def test_reallocation_applies_before_interval(self):
        """apply_config under a scaled topology must keep placements inside
        the tenant's allocation: per-node replica counts respect the scaled
        capacities (placement overflow would mark the config infeasible)."""
        sess = api.FleetSession.from_spec(_fleet_spec())
        infeasible = []
        sess.serve(
            horizon=40,
            on_step=lambda fleet, interval: infeasible.extend(
                info["infeasible"] for info in interval.values()
            ),
        )
        assert not any(infeasible)

    def test_determinism(self):
        r1 = api.FleetSession.from_spec(_fleet_spec()).serve(horizon=30)
        r2 = api.FleetSession.from_spec(_fleet_spec()).serve(horizon=30)
        assert r1["rewards"] == r2["rewards"]
        s1, s2 = r1["summary"], r2["summary"]
        assert s1["tenants"] == s2["tenants"]
        f1 = {k: v for k, v in s1["fleet"].items() if k != "events_per_s"}
        f2 = {k: v for k, v in s2["fleet"].items() if k != "events_per_s"}
        assert f1 == f2


class TestFleetSessionShape:
    def test_report_structure(self):
        spec = _fleet_spec()
        rep = api.FleetSession.from_spec(spec).serve(horizon=20)
        n_steps = 20 // ADAPTATION_INTERVAL
        assert set(rep["rewards"]) == {t.name for t in spec.tenants}
        for r in rep["rewards"].values():
            assert len(r) == n_steps
        f = rep["summary"]["fleet"]
        assert f["tenants"] == 3
        assert f["events"] > 0 and f["events_per_s"] > 0
        # the JSON round trip of the report must hold (CI artifact)
        json.dumps(rep)

    def test_build_fleet_direct(self):
        """The serving-layer entry point works without the api facade."""
        spec = _fleet_spec()
        entries = []
        for t in spec.tenants:
            pipe = spec.tenant_pipeline(t).build()
            ctrl = api.controller_factory(t.controller.name)(
                t.controller, pipe, None
            )
            entries.append(
                {
                    "name": t.name,
                    "pipe": pipe,
                    "arrivals": t.scenario.build_arrivals(),
                    "controller": ctrl,
                    "priority": t.priority,
                }
            )
        fleet = build_fleet(
            entries, admission_limit=spec.admission_limit, horizon=20
        )
        fleet.step_interval()
        fleet.step_interval()
        fleet.drain()
        s = fleet.summary()
        assert s["fleet"]["offered"] == sum(
            t["arrived"] for t in s["tenants"].values()
        )


# Each tenant's virtual-time tail and shed count over 60 s, recorded from the
# fleet before its CPU speed benchmark was retired: "registered" serves the
# fleet as registered, "overloaded" at 400/300/250 req/s under an admission
# limit of 3000 (that benchmark's load, about 1e5 requests over 120 s).
OVERLOAD_RATES = {"interactive": 400.0, "analytics": 300.0, "batch": 250.0}
RECORDED_TAILS = {
    "registered": {
        "interactive": {"served": 1347, "shed": 0, "p99": 3.131542181093147},
        "analytics": {"served": 897, "shed": 0, "p99": 3.7606152261006485},
        "batch": {"served": 1599, "shed": 26, "p99": 1.6443374112516966},
    },
    "overloaded": {
        "interactive": {"served": 21316, "shed": 0, "p99": 3.138853665491759},
        "analytics": {"served": 17318, "shed": 751, "p99": 3.7062383204866527},
        "batch": {"served": 15442, "shed": 4152, "p99": 1.0365989816756063},
    },
}


@pytest.mark.parametrize("load", sorted(RECORDED_TAILS))
def test_tenant_tails_and_shed_match_the_record(load):
    spec = _fleet_spec()
    if load == "overloaded":
        tenants = tuple(
            api.replace(
                t, scenario=api.replace(t.scenario, rate=OVERLOAD_RATES[t.name])
            )
            for t in spec.tenants
        )
        spec = api.replace(spec, tenants=tenants, admission_limit=3000.0)
    rep = api.FleetSession.from_spec(spec).serve(horizon=60)
    got = rep["summary"]["tenants"]
    assert set(got) == set(RECORDED_TAILS[load])
    for name, want in RECORDED_TAILS[load].items():
        assert got[name]["served"] == want["served"], name
        assert got[name]["shed"] == want["shed"], name
        assert got[name]["p99"] == pytest.approx(want["p99"], rel=1e-9), name
