"""Chaos scenario 18 (ISSUE 18, docs/capacity.md): flash crowd + replica
kill + abusive tenant, SIMULTANEOUSLY, through 3 replicas behind 2 peered
router edges — driven by the real open-loop generator, judged by the real
federated capacity surface.

What must hold, all at once:

- the SLO *page* (fast-burn, user-perceived, both edges) stays silent
  through the whole storm;
- every abuser shed is accounted: client-observed 429s ≡ the replicas'
  demand ledgers ≡ the federated capacity report's shed ledger (minus
  exactly the killed replica, which the report NAMES as failed);
- ``GET /v1/autoscale`` on a router edge recommends MORE replicas while
  the crowd burns and converges back to the floor after it passes;
- the converged recommendation (< live replicas) is ACTUATED through the
  PR 11 drain/lease-handoff machinery, with zero lease-scoped 5xx — the
  first scale-in this repo has ever exercised under load."""

import asyncio
import time

import httpx
import pytest
from aiohttp import web

from bee_code_interpreter_tpu.fleet import FleetRouter, create_router_app
from bee_code_interpreter_tpu.loadgen import (
    FlashCrowd,
    OpenLoopGenerator,
    Steady,
    TrafficMix,
)
from bee_code_interpreter_tpu.tenancy import (
    TENANT_HEADER,
    TenantRegistry,
    parse_tenants,
)
from tests.fakes import ReplicaStack, free_port

pytestmark = pytest.mark.chaos

SPEC = "abuser:weight=1:rps=2:burst=2,victim:weight=4"


async def test_chaos18_flash_crowd_replica_kill_abusive_tenant(tmp_path):
    shared_root = tmp_path / "shared-objects"
    port_a, port_b = free_port(), free_port()
    url_a = f"http://127.0.0.1:{port_a}"
    url_b = f"http://127.0.0.1:{port_b}"
    # Short demand windows so the recommendation can converge back within
    # test-scale seconds (the production default is 120s).
    stacks = [
        await ReplicaStack(
            f"r{i}",
            tmp_path,
            shared_root,
            tenants=SPEC,
            autoscale_window_s=4.0,
        ).start()
        for i in range(3)
    ]

    def make_router(rid, peer_name, peer_url):
        return FleetRouter(
            [(s.name, s.base_url) for s in stacks],
            # a refresh may take a second and a replica is dead after five
            # missed ones: on a host that six test workers share, a horizon
            # of one second declared the whole fleet dead under the crowd
            # and answered it 503 "no eligible replicas"
            refresh_interval_s=0.5,
            dead_after_s=2.5,
            tenancy=TenantRegistry(parse_tenants(SPEC)),
            peers=[(peer_name, peer_url)],
            router_id=rid,
        )

    router_a = make_router("A", "b", url_b)
    router_b = make_router("B", "a", url_a)
    runners = []
    for router, port in ((router_a, port_a), (router_b, port_b)):
        runner = web.AppRunner(create_router_app(router))
        await runner.setup()
        await web.TCPSite(runner, "127.0.0.1", port).start()
        await router.refresh_once()
        router.start()
        runners.append(runner)
    client = httpx.AsyncClient(timeout=30.0)
    session_statuses: list[int] = []

    async def autoscale_when(settled, within_s: float = 30.0) -> dict:
        """Edge A's federated ``/v1/autoscale`` document once ``settled``
        holds of it: the test waits for the state it names, and the clock
        only bounds how long a broken fleet may take to fail."""
        deadline = time.monotonic() + within_s
        while True:
            body = (await client.get(f"{url_a}/v1/autoscale")).json()
            if settled(body) or time.monotonic() > deadline:
                return body
            await asyncio.sleep(0.1)

    try:
        # --- quiet fleet: the federated document already recommends the
        # floor, and knows its own size
        body = (await client.get(f"{url_a}/v1/autoscale")).json()
        assert body["replica_states"]["healthy"] == 3
        assert body["recommendation"]["target_replicas"] == 1
        assert body["recommendation"]["reason"] == "idle"
        assert body["replicas_reporting"] == ["r0", "r1", "r2"]

        # --- one live session through edge A, state written
        response = await client.post(f"{url_a}/v1/sessions", json={})
        assert response.status_code == 200, response.text
        session_id = response.json()["session_id"]
        response = await client.post(
            f"{url_a}/v1/sessions/{session_id}/execute",
            json={"source_code": "open('state.txt', 'w').write('eighteen')"},
        )
        assert response.status_code == 200, response.text

        async def session_turn() -> None:
            resp = await client.post(
                f"{url_a}/v1/sessions/{session_id}/execute",
                json={"source_code": "print(open('state.txt').read())"},
            )
            session_statuses.append(resp.status_code)

        # --- the storm: a 10x flash crowd open-loop through BOTH edges,
        # an abuser flood through edge B, a session trickle, and a hard
        # replica kill in the middle of it all
        crowd_shape = FlashCrowd(
            base_rps=3.0,
            duration_s=5.0,
            crowd_start_s=1.0,
            crowd_s=2.0,
            multiplier=10.0,
        )
        crowd_mix = TrafficMix(
            kinds=(("execute", 9.0), ("stream", 1.0)), seed=18
        )
        crowd_a = OpenLoopGenerator(client, url_a, mix=crowd_mix)
        crowd_b = OpenLoopGenerator(client, url_b, mix=crowd_mix)
        abuse_gen = OpenLoopGenerator(
            client,
            url_b,
            mix=TrafficMix(
                kinds=(("execute", 1.0),),
                tenants=[("abuser", 1.0)],
                seed=18,
            ),
        )

        async def storm_side_effects() -> None:
            # Mid-crowd (t≈2s): hard-kill a replica that does NOT hold the
            # session pin — the router must absorb it invisibly.
            await asyncio.sleep(2.0)
            pin = router_a.sessions[session_id].replica
            victim = next(s for s in stacks if s.name != pin)
            await victim.stop(hard=True)
            storm_side_effects.killed = victim.name
            await session_turn()
            # Scrape the federated recommendation WHILE the crowd burns
            # (the demand windows are seconds-short by design; a scrape
            # deferred to after the generators drain can see the peak
            # already decayed on a slow box): as soon as the edge's view
            # of the killed replica has aged out.
            storm_side_effects.mid_storm = await autoscale_when(
                lambda body: victim.name in body["replicas_failed"]
                and body["replica_states"]["healthy"] == 2
            )

        crowd_task_a = asyncio.create_task(
            crowd_a.run(crowd_shape, label="crowd-a", seed=1)
        )
        crowd_task_b = asyncio.create_task(
            crowd_b.run(crowd_shape, label="crowd-b", seed=2)
        )
        abuse_task = asyncio.create_task(
            abuse_gen.run(Steady(rps=18.0, duration_s=2.0), label="abuse")
        )
        kill_task = asyncio.create_task(storm_side_effects())
        await session_turn()
        result_a, result_b, abuse, _ = await asyncio.gather(
            crowd_task_a, crowd_task_b, abuse_task, kill_task
        )
        killed = storm_side_effects.killed

        # --- crowd verdict: open-loop offered everything on schedule; the
        # kill cost retries, not user-visible failures (the error allowance
        # absorbs CPU-starved in-flight casualties of the kill itself)
        for result in (result_a, result_b):
            assert result.sent == result.offered
            assert result.errors <= max(2, result.sent // 25), (
                result.to_dict()
            )

        # --- recommendation DURING the storm: the federated edge wants a
        # bigger fleet than it has left
        body = storm_side_effects.mid_storm
        rec = body["recommendation"]
        assert killed in body["replicas_failed"]
        healthy_now = body["replica_states"]["healthy"]
        assert healthy_now == 2
        assert rec["current_replicas"] == healthy_now
        assert rec["target_replicas"] > healthy_now, rec
        assert rec["reason"] == "forecast"

        # --- SLO page silent at BOTH edges, and fleet-wide
        for edge_url in (url_a, url_b):
            slo = (await client.get(f"{edge_url}/v1/slo")).json()
            assert slo["fast_burn_alerting"] is False
            assert slo["fleet_fast_burn"] is False

        # --- every abuser shed accounted, exactly once, fleet-wide:
        # client-observed 429s == the demand ledgers (the killed replica's
        # in-process ledger included), and the federated capacity report
        # carries the surviving share while NAMING the gap
        client_429 = abuse.shed_ledger().get("abuser", 0)
        assert client_429 > 0
        ledger_total = sum(
            s.demand.sheds_by_tenant.get("abuser", 0) for s in stacks
        )
        assert client_429 == ledger_total
        surviving = sum(
            s.demand.sheds_by_tenant.get("abuser", 0)
            for s in stacks
            if s.name != killed
        )
        # Fresh post-storm scrape: the per-tenant shed counters are
        # CUMULATIVE, so this accounting does not race the window decay.
        body = (await client.get(f"{url_a}/v1/autoscale")).json()
        assert killed in body["replicas_failed"]
        reported = (
            body["demand"]["by_tenant"].get("abuser", {}).get("sheds", 0)
        )
        assert reported == surviving
        # The abuser never touched the victim's session lane: zero
        # lease-scoped 5xx (a 429 under the crowd is the admission gate
        # doing its job on a saturated replica — the lease survives it).
        assert all(status < 500 for status in session_statuses), (
            session_statuses
        )

        # --- the crowd passes: the recommendation converges back to the
        # floor once the demand windows drain
        body = await autoscale_when(
            lambda body: body["recommendation"]["target_replicas"] == 1
        )
        rec = body["recommendation"]
        assert rec["target_replicas"] == 1, rec
        # "idle" once every window drained; "forecast" while a trickle of
        # residual demand still needs (exactly) the floor — converged
        # either way.
        assert rec["reason"] in ("idle", "forecast"), rec

        # --- ACTUATE the scale-in the document asks for (target 1 < 2
        # healthy), through drain/lease-handoff: drain the replica holding
        # the session pin — its lease must hand off with zero 5xx
        assert rec["target_replicas"] < body["replica_states"]["healthy"]
        pin = router_a.sessions[session_id].replica
        response = await client.post(
            f"{url_a}/v1/fleet/replicas/{pin}/drain"
        )
        assert response.status_code == 200, response.text
        tally = response.json()
        assert tally["migrated"] == 1 and tally["failed"] == 0
        assert router_a.sessions[session_id].replica != pin
        await session_turn()
        # The drained replica retires; the fleet is now the recommended
        # size and the session (same public id, state intact) still serves.
        drained = next(s for s in stacks if s.name == pin)
        await drained.stop()
        # until the edge's view of it has aged out
        body = await autoscale_when(
            lambda body: body["replica_states"]["healthy"] == 1
        )
        response = await client.post(
            f"{url_a}/v1/sessions/{session_id}/execute",
            json={"source_code": "print(open('state.txt').read())"},
        )
        session_statuses.append(response.status_code)
        assert response.status_code == 200, response.text
        assert "eighteen" in response.json()["stdout"]
        assert all(status < 500 for status in session_statuses), (
            session_statuses
        )
        assert len(session_statuses) >= 4

        assert body["replica_states"]["healthy"] == 1
        assert body["recommendation"]["target_replicas"] == 1
        assert (
            body["recommendation"]["target_replicas"]
            == body["replica_states"]["healthy"]
        )

        # --- abusive-tenant sheds were tenant-scoped, never re-walked
        retries = router_b.metrics.metrics[
            "bci_router_retries_total"
        ]._values
        assert retries.get((("reason", "shed"),), 0) == 0
    finally:
        await client.aclose()
        for runner in runners:
            await runner.cleanup()
        await router_a.stop()
        await router_b.stop()
        for stack in stacks:
            await stack.stop()
