"""List pagination + the bridge's mass-eviction guard.

The reference does one unpaginated GET per resource and trusts whatever
came back (k8s_api_client.cc:100-160). Against an apiserver that chunks
its lists (``limit``/``continue``) that drops every item after page one,
and a truncated response reads as mass deletion — one bad poll would
evict most of the scheduler's state. These tests pin both defenses:

- the client follows ``metadata.continue`` tokens until the list is
  complete (round-3 verdict, Next #7);
- an item unchanged since the previous complete list returns the object
  parsed from it then; a changed one, a failed list or a deleted key
  does not reuse it;
- the bridge holds a >50% disappearance for ``SHRINK_STRIKES``
  consecutive polls before honoring it, and still honors a persistent
  (real) shrink afterwards.
"""

from __future__ import annotations

import pytest

from poseidon_tpu.apiclient import FakeApiServer, K8sApiClient
from poseidon_tpu.apiclient.client import ApiError
from poseidon_tpu.bridge import SchedulerBridge
from poseidon_tpu.bridge.bridge import SHRINK_STRIKES
from poseidon_tpu.cluster import TaskPhase


def _fill(server: FakeApiServer, nodes: int, pods: int) -> None:
    for i in range(nodes):
        server.add_node(f"node-{i:03d}", rack=f"rack-{i % 3}")
    for i in range(pods):
        server.add_pod(f"pod-{i:03d}")


class TestClientPagination:
    def test_follows_continue_tokens(self):
        with FakeApiServer() as server:
            _fill(server, nodes=23, pods=57)
            client = K8sApiClient(port=server.port, page_limit=10)
            nodes = client.all_nodes()
            pods = client.all_pods()
        assert sorted(n.name for n in nodes) == sorted(
            f"node-{i:03d}" for i in range(23)
        )
        assert sorted(p.uid for p in pods) == sorted(
            f"default/pod-{i:03d}" for i in range(57)
        )

    def test_single_page_when_under_limit(self):
        with FakeApiServer() as server:
            _fill(server, nodes=3, pods=4)
            client = K8sApiClient(port=server.port, page_limit=500)
            before = server.requests_served
            assert len(client.all_nodes()) == 3
            assert server.requests_served == before + 1

    def test_selector_applies_across_pages(self):
        with FakeApiServer() as server:
            _fill(server, nodes=12, pods=0)
            client = K8sApiClient(port=server.port, page_limit=5)
            rack0 = client.nodes_with_label("rack=rack-0")
        assert sorted(n.name for n in rack0) == sorted(
            f"node-{i:03d}" for i in range(12) if i % 3 == 0
        )


def _by_key(objs):
    return {getattr(o, "uid", None) or o.name: o for o in objs}


class TestParsedItemReuse:
    """``K8sApiClient._list_parsed``: an item ``==`` to the previous
    complete list's returns the SAME frozen object (the bridge reads
    that identity as "unchanged"); anything else is parsed afresh."""

    @pytest.mark.parametrize("resource", ["nodes", "pods"])
    def test_unchanged_items_return_the_identical_object(self, resource):
        with FakeApiServer() as server:
            _fill(server, nodes=12, pods=12)
            client = K8sApiClient(port=server.port, page_limit=5)
            first = getattr(client, f"all_{resource}")()
            again, _rv = getattr(client, f"{resource}_with_rv")()
        assert len(first) == len(again) == 12
        assert all(x is y for x, y in zip(first, again))

    @pytest.mark.parametrize(
        "change", ["nodeName", "phase", "annotation", "resourceVersion"])
    def test_a_changed_pod_item_is_parsed_afresh(self, change):
        with FakeApiServer() as server:
            _fill(server, nodes=3, pods=6)
            client = K8sApiClient(port=server.port, page_limit=4)
            first = _by_key(client.all_pods())
            uid = "default/pod-002"
            if change == "nodeName":
                assert client.bind_pod_to_node(uid, "node-001")
            elif change == "phase":
                server.succeed_pod("pod-002")
            elif change == "annotation":
                server.add_pod("pod-002", data_prefs={"node-000": 5})
            else:
                server.pods[uid]["metadata"]["resourceVersion"] = "7"
            again = _by_key(client.all_pods())
        assert again[uid] is not first[uid]
        if change == "resourceVersion":
            assert again[uid] == first[uid]     # an equal, fresh parse
        else:
            assert again[uid] != first[uid]
        assert all(again[k] is first[k] for k in first if k != uid)

    def test_a_changed_node_item_is_parsed_afresh(self):
        with FakeApiServer() as server:
            _fill(server, nodes=6, pods=0)
            client = K8sApiClient(port=server.port, page_limit=4)
            first = _by_key(client.all_nodes())
            server.add_node("node-004", pods=12, rack="rack-1")
            again = _by_key(client.all_nodes())
        assert again["node-004"] is not first["node-004"]
        assert again["node-004"].max_tasks == 12
        assert all(again[k] is first[k] for k in first if k != "node-004")

    @pytest.mark.parametrize("resource", ["nodes", "pods"])
    def test_a_list_failing_mid_pages_leaves_the_held_items(
            self, resource):
        with FakeApiServer() as server:
            _fill(server, nodes=12, pods=12)
            client = K8sApiClient(port=server.port, page_limit=5,
                                  retries=0)
            listing = getattr(client, f"all_{resource}")
            first = listing()
            request = client._request
            calls = []

            def second_page_fails(path, *a, **kw):
                calls.append(path)
                if len(calls) == 2:
                    raise ApiError("injected", code=503)
                return request(path, *a, **kw)

            client._request = second_page_fails
            with pytest.raises(ApiError):
                listing()
            client._request = request
            again = listing()
        assert len(calls) == 2
        assert all(x is y for x, y in zip(first, again))

    @pytest.mark.parametrize("resource", ["nodes", "pods"])
    def test_a_deleted_key_leaves_the_held_items(self, resource):
        with FakeApiServer() as server:
            _fill(server, nodes=4, pods=4)
            client = K8sApiClient(port=server.port)
            listing = getattr(client, f"all_{resource}")
            store = server.nodes if resource == "nodes" else server.pods
            key = next(iter(store))
            first = _by_key(listing())
            item = store.pop(key)
            name = key.split("/")[-1]
            assert name not in {k.split("/")[-1]
                                for k in _by_key(listing())}
            store[key] = item             # back, unchanged
            again = _by_key(listing())
        k = next(k for k in again if k.split("/")[-1] == name)
        assert again[k] == first[k] and again[k] is not first[k]


class TestMassEvictionGuard:
    def _observe(self, bridge, client):
        bridge.observe_nodes(client.all_nodes())
        bridge.observe_pods(client.all_pods())

    def test_truncated_snapshot_does_not_evict(self):
        with FakeApiServer() as server:
            _fill(server, nodes=10, pods=40)
            client = K8sApiClient(port=server.port)
            bridge = SchedulerBridge()
            self._observe(bridge, client)
            assert len(bridge.machines) == 10
            assert len(bridge.tasks) == 40

            # one faulty poll: only 2 nodes / 5 pods come back, with no
            # continue token — a partial snapshot masquerading as full
            server.truncate_lists(2)
            bridge.observe_nodes(client.all_nodes())
            server.truncate_lists(5)
            bridge.observe_pods(client.all_pods())
            assert len(bridge.machines) == 10, "held, not evicted"
            assert len(bridge.tasks) == 40, "held, not retired"

            # recovery: the next full poll clears the strike counters
            server.truncate_lists(0)
            self._observe(bridge, client)
            assert len(bridge.machines) == 10
            assert len(bridge.tasks) == 40
            assert bridge._node_shrink_strikes == 0
            assert bridge._pod_shrink_strikes == 0

    def test_persistent_shrink_is_honored(self):
        with FakeApiServer() as server:
            _fill(server, nodes=10, pods=40)
            client = K8sApiClient(port=server.port)
            bridge = SchedulerBridge()
            self._observe(bridge, client)

            # a real teardown: most pods deleted, most nodes drained
            for i in range(3, 10):
                server.drop_node(f"node-{i:03d}")
            with server._lock:
                for i in range(10, 40):
                    server.pods.pop(f"default/pod-{i:03d}", None)

            for _ in range(SHRINK_STRIKES - 1):
                self._observe(bridge, client)
                assert len(bridge.machines) == 10  # still holding
                assert len(bridge.tasks) == 40
            self._observe(bridge, client)  # strike limit reached
            assert len(bridge.machines) == 3
            assert len(bridge.tasks) == 10

    def test_truncated_snapshot_with_new_names_still_held(self):
        # the guard's denominator is the PRE-upsert known count: a
        # truncated poll that also carries new names must not inflate
        # it past the >50% threshold (mid-rollover partial cache)
        with FakeApiServer() as server:
            _fill(server, nodes=10, pods=0)
            client = K8sApiClient(port=server.port)
            bridge = SchedulerBridge()
            bridge.observe_nodes(client.all_nodes())
            assert len(bridge.machines) == 10
            # 4 survivors + 3 brand-new nodes; 6 of 10 known vanish
            with server._lock:
                survivors = {f"node-{i:03d}" for i in range(4)}
                for name in list(server.nodes):
                    if name not in survivors:
                        del server.nodes[name]
            for i in range(3):
                server.add_node(f"fresh-{i}")
            bridge.observe_nodes(client.all_nodes())
            # held: the 6 missing stay known, the 3 new are upserted
            assert len(bridge.machines) == 13
            assert bridge._node_shrink_strikes == 1

    def test_small_clusters_evict_immediately(self):
        # the guard only arms at SHRINK_MIN_KNOWN entities: a 3-node dev
        # cluster dropping 2 nodes is ordinary, not implausible
        with FakeApiServer() as server:
            _fill(server, nodes=3, pods=4)
            client = K8sApiClient(port=server.port)
            bridge = SchedulerBridge()
            self._observe(bridge, client)
            server.drop_node("node-001")
            server.drop_node("node-002")
            self._observe(bridge, client)
            assert set(bridge.machines) == {"node-000"}

    def test_plausible_shrink_unaffected(self):
        with FakeApiServer() as server:
            _fill(server, nodes=10, pods=40)
            client = K8sApiClient(port=server.port)
            bridge = SchedulerBridge()
            self._observe(bridge, client)
            server.drop_node("node-009")
            with server._lock:
                for i in range(35, 40):
                    server.pods.pop(f"default/pod-{i:03d}", None)
            self._observe(bridge, client)
            assert len(bridge.machines) == 9
            assert len(bridge.tasks) == 35

    def test_held_pods_keep_phase(self):
        # a held pod snapshot must not corrupt task phases: pods absent
        # from the truncated list keep their recorded state
        with FakeApiServer() as server:
            _fill(server, nodes=10, pods=40)
            client = K8sApiClient(port=server.port)
            bridge = SchedulerBridge()
            self._observe(bridge, client)
            server.truncate_lists(5)
            bridge.observe_pods(client.all_pods())
            assert all(
                t.phase == TaskPhase.PENDING
                for t in bridge.tasks.values()
            )
