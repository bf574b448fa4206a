"""The daemon loop joins each round in the tick that dispatched it.

``run_loop`` dispatches a round's solve, joins it at once and POSTs the
round's bindings before the next LIST, so no decided binding waits on
the host for another tick. ``--round_pipeline`` is still accepted (old
flagfiles carry it) and both its values run that one tick: on one pod
script they decide the same bindings at the same costs.
"""

import pytest

from poseidon_tpu import cli, solver
from poseidon_tpu.apiclient import FakeApiServer, K8sApiClient

ROUNDS = 6


def _script(server, k, rounds):
    """The pod script, applied before the loop's k-th pod LIST (0-based):
    four new pods, and two pods of the round decided three LISTs
    earlier complete."""
    if k >= 3:
        for uid in sorted(rounds[k - 3]["bindings"])[:2]:
            server.succeed_pod(uid)
    for j in range(4):
        server.add_pod(f"p{k}-{j}", cpu="250m", memory="256Mi",
                       job=f"job{k % 2}",
                       data_prefs={f"n{(k + j) % 3}": 40} if j % 2 else None)


def _drive(monkeypatch, pipeline):
    """``run_loop`` for ``ROUNDS`` rounds over the script with
    ``--round_pipeline=<pipeline>``. Returns the rounds as the hook saw
    them and, per pod LIST, the bindings the apiserver held when it was
    served."""
    rounds, lists = [], []
    with FakeApiServer() as server:
        for i in range(3):
            server.add_node(f"n{i}", cpu="8", memory="16Gi", pods=4,
                            rack=f"rack{i % 2}")
        real = K8sApiClient.all_pods

        def all_pods(self):
            lists.append(set(uid for uid, _m in server.bindings))
            _script(server, len(lists) - 1, rounds)
            return real(self)

        def hook(_n, result, _bridge):
            rounds.append({
                "bindings": dict(result.bindings),
                "cost": result.stats.cost,
                "pending": result.stats.pods_pending,
                "backend": result.stats.backend,
                "at_server": set(uid for uid, _m in server.bindings),
            })

        monkeypatch.setattr(K8sApiClient, "all_pods", all_pods)
        rc = cli.run_loop(cli.parse_args([
            "--k8s_apiserver_host=127.0.0.1",
            f"--k8s_apiserver_port={server.port}",
            "--flow_scheduling_cost_model=quincy",
            "--polling_frequency=1000",
            f"--max_rounds={ROUNDS}",
            f"--round_pipeline={pipeline}",
        ]), round_hook=hook)
        posted = [uid for uid, _m in server.bindings]
    assert rc == 0
    assert len(rounds) == ROUNDS
    # every decided binding reached the apiserver exactly once
    decided = [u for r in rounds for u in r["bindings"]]
    assert sorted(posted) == sorted(decided)
    return rounds, lists


@pytest.fixture(scope="module", params=["oracle", "dense"])
def runs(request):
    """Both flag values on one backend: the small-instance oracle the
    tiny cluster is routed to, or the resident dense solve (the routing
    turned off), whose fetch runs on the background thread."""
    mp = pytest.MonkeyPatch()
    try:
        out = {}
        for pipeline in ("true", "false"):
            if request.param == "dense":
                mp.setattr(solver, "is_small_instance", lambda _t, _m: False)
            out[pipeline] = _drive(mp, pipeline)
            mp.undo()
        backends = {r["backend"] for rounds, _ in out.values()
                    for r in rounds if r["pending"]}
        assert backends == {"oracle": {"oracle:small-instance"},
                            "dense": {"dense_auction"}}[request.param]
        return out
    finally:
        mp.undo()


@pytest.mark.parametrize("pipeline", ["true", "false"])
def test_each_round_posts_before_the_next_list(runs, pipeline):
    rounds, lists = runs[pipeline]
    assert any(r["bindings"] for r in rounds)
    for n, r in enumerate(rounds[:-1]):
        # the hook runs between decision and actuation; the bindings
        # are on the wire before the LIST the next round is built from,
        # and so before the next round's hook
        assert not set(r["bindings"]) & r["at_server"]
        assert set(r["bindings"]) <= lists[n + 1]
        assert set(r["bindings"]) <= rounds[n + 1]["at_server"]
    # one LIST per round: nothing is carried past the last one
    assert len(lists) == ROUNDS


def test_round_pipeline_values_decide_alike(runs):
    def decisions(pipeline):
        return [(r["bindings"], r["cost"], r["pending"])
                for r in runs[pipeline][0]]

    assert decisions("true") == decisions("false")
    # the script outgrows the 12 seats: some rounds leave pods waiting
    assert any(len(b) < p for b, _c, p in decisions("true"))
