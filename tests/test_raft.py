"""Raft cluster tests over the in-memory transport.

Pattern follows the reference's in-process multi-node harnesses
(KVRangeStoreTestCluster + raft unit tests, SURVEY.md §4): N real RaftNodes,
fake transport, manual ticks, fault injection via partitions.
"""

import asyncio
import random

import pytest

from bifromq_tpu.raft.node import NotLeaderError, RaftNode, Role
from bifromq_tpu.raft.transport import InMemTransport

pytestmark = pytest.mark.asyncio


class Cluster:
    def __init__(self, n: int, seed: int = 0) -> None:
        self.transport = InMemTransport()
        self.ids = [f"n{i}" for i in range(n)]
        self.applied = {nid: [] for nid in self.ids}
        self.state = {nid: [] for nid in self.ids}  # fsm = list of payloads
        self.nodes = {}
        rng = random.Random(seed)
        for nid in self.ids:
            node = RaftNode(
                nid, list(self.ids), self.transport,
                apply_cb=lambda e, nid=nid: self.applied[nid].append(
                    (e.index, e.data)),
                snapshot_cb=lambda nid=nid: repr(self.applied[nid]).encode(),
                restore_cb=lambda b, nid=nid: self.applied[nid].__setitem__(
                    slice(None), eval(b.decode())),
                rng=random.Random(rng.randint(0, 1 << 30)))
            self.transport.register(node)
            self.nodes[nid] = node

    def step(self, ticks: int = 1) -> None:
        for _ in range(ticks):
            for node in self.nodes.values():
                node.tick()
            self.transport.pump()

    def run_until(self, cond, max_ticks: int = 500) -> None:
        for _ in range(max_ticks):
            if cond():
                return
            self.step()
        raise AssertionError("condition not reached")

    def leader(self):
        leaders = [n for n in self.nodes.values()
                   if n.role == Role.LEADER and not n.stopped]
        # among live leaders, the highest term wins (stale leaders linger
        # in partitions)
        return max(leaders, key=lambda n: n.term) if leaders else None

    def elect(self):
        self.run_until(lambda: self.leader() is not None)
        return self.leader()

    async def propose(self, data: bytes) -> int:
        leader = self.leader()
        fut = leader.propose(data)
        self.run_until(lambda: fut.done())
        return await fut


class TestElection:
    async def test_single_leader_elected(self):
        c = Cluster(3)
        leader = c.elect()
        assert leader is not None
        # exactly one leader at that term
        assert sum(1 for n in c.nodes.values()
                   if n.role == Role.LEADER and n.term == leader.term) == 1

    async def test_reelection_after_leader_death(self):
        c = Cluster(3)
        first = c.elect()
        c.transport.kill(first.id)
        c.run_until(lambda: c.leader() is not None
                    and c.leader().id != first.id)
        assert c.leader().term > first.term

    async def test_no_quorum_no_leader(self):
        c = Cluster(3)
        c.elect()
        c.transport.kill(c.ids[0])
        c.transport.kill(c.ids[1])
        survivor = c.nodes[c.ids[2]]
        for _ in range(100):
            c.step()
        assert survivor.role != Role.LEADER or survivor.stopped

    async def test_five_node_cluster(self):
        c = Cluster(5)
        assert c.elect() is not None


class TestReplication:
    async def test_propose_commits_everywhere(self):
        c = Cluster(3)
        c.elect()
        idx = await c.propose(b"cmd1")
        assert idx > 0
        c.run_until(lambda: all(
            (idx, b"cmd1") in c.applied[nid] for nid in c.ids))
        # identical apply order
        assert len({tuple(c.applied[nid]) for nid in c.ids}) == 1

    async def test_many_proposals_in_order(self):
        c = Cluster(3)
        c.elect()
        for i in range(30):
            await c.propose(f"c{i}".encode())
        c.run_until(lambda: all(len(c.applied[nid]) >= 30 for nid in c.ids))
        for nid in c.ids:
            datas = [d for _, d in c.applied[nid]]
            assert datas == [f"c{i}".encode() for i in range(30)]

    async def test_propose_on_follower_rejected(self):
        c = Cluster(3)
        leader = c.elect()
        follower = next(n for n in c.nodes.values() if n is not leader)
        with pytest.raises(NotLeaderError) as ei:
            await follower.propose(b"x")
        assert ei.value.leader_hint == leader.id

    async def test_commit_survives_leader_change(self):
        c = Cluster(3)
        first = c.elect()
        await c.propose(b"before")
        c.transport.kill(first.id)
        c.run_until(lambda: c.leader() is not None
                    and c.leader().id != first.id)
        fut = c.leader().propose(b"after")
        c.run_until(lambda: fut.done())
        await fut
        live = [nid for nid in c.ids if nid != first.id]
        c.run_until(lambda: all(
            [d for _, d in c.applied[nid] if d in (b"before", b"after")]
            == [b"before", b"after"] for nid in live))


class TestPartition:
    async def test_minority_partition_cannot_commit(self):
        c = Cluster(5)
        leader = c.elect()
        minority = {leader.id, next(i for i in c.ids if i != leader.id)}
        majority = set(c.ids) - minority
        c.transport.partition(minority, majority)
        fut = leader.propose(b"stale")
        for _ in range(80):
            c.step()
        assert not fut.done()  # never commits in minority
        # majority elects a new leader and commits
        c.run_until(lambda: any(
            n.role == Role.LEADER and n.id in majority and not n.stopped
            for n in c.nodes.values()))
        new_leader = next(n for n in c.nodes.values()
                          if n.role == Role.LEADER and n.id in majority)
        fut2 = new_leader.propose(b"fresh")
        c.run_until(lambda: fut2.done())
        await fut2

    async def test_heal_converges_logs(self):
        c = Cluster(5)
        leader = c.elect()
        minority = {leader.id}
        majority = set(c.ids) - minority
        c.transport.partition(minority, majority)
        leader.propose(b"lost")  # uncommitted on old leader
        c.run_until(lambda: any(
            n.role == Role.LEADER and n.id in majority for n in
            c.nodes.values()))
        new_leader = max((n for n in c.nodes.values()
                          if n.role == Role.LEADER and n.id in majority),
                         key=lambda n: n.term)
        fut = new_leader.propose(b"kept")
        c.run_until(lambda: fut.done())
        c.transport.heal()
        c.run_until(lambda: all(
            b"kept" in [d for _, d in c.applied[nid]] for nid in c.ids))
        # the uncommitted entry must not appear anywhere
        for nid in c.ids:
            assert b"lost" not in [d for _, d in c.applied[nid]]


class TestReadIndex:
    async def test_read_index_confirms_leadership(self):
        c = Cluster(3)
        leader = c.elect()
        await c.propose(b"x")
        fut = leader.read_index()
        c.run_until(lambda: fut.done())
        assert await fut >= 1

    async def test_read_index_single_voter(self):
        c = Cluster(1)
        leader = c.elect()
        fut = leader.read_index()
        c.run_until(lambda: fut.done())
        await fut


class TestSnapshot:
    async def test_lagging_follower_catches_up_via_snapshot(self):
        c = Cluster(3)
        leader = c.elect()
        straggler = next(nid for nid in c.ids if nid != leader.id)
        c.transport.partition({straggler}, set(c.ids) - {straggler})
        # push enough entries to trigger compaction on the leader
        for i in range(RaftNode.SNAPSHOT_THRESHOLD + 60):
            await c.propose(f"s{i}".encode())
        assert c.leader().snap.last_index > 0  # compacted
        c.transport.heal()
        c.run_until(lambda: c.nodes[straggler].commit_index
                    >= c.leader().commit_index, max_ticks=2000)
        # straggler restored state via snapshot + tail replication
        assert c.applied[straggler][-1] == c.applied[c.leader().id][-1]


    @pytest.mark.parametrize("records", [0, 16 * 400, 16 * 4000])
    async def test_compaction_is_paced_by_the_fsm_size(self, records):
        """Cutting a snapshot costs O(FSM state) on the serving thread, so
        the log may hold one entry per ``STATE_RECORDS_PER_LOG_ENTRY``
        records before it is compacted (never fewer than
        ``SNAPSHOT_THRESHOLD``): a 1M-key range is not serialised every
        256 SUBSCRIBEs."""
        c = Cluster(1)
        leader = c.elect()
        leader.state_len_cb = lambda: records
        allowed = max(RaftNode.SNAPSHOT_THRESHOLD,
                      records // RaftNode.STATE_RECORDS_PER_LOG_ENTRY)
        n = RaftNode.SNAPSHOT_THRESHOLD + 300
        for i in range(n):
            await c.propose(f"p{i}".encode())
        assert (leader.snap.last_index > 0) == (n > allowed)
        assert len(leader.log) <= allowed
        assert [d for _i, d in c.applied[leader.id]][-1] == f"p{n - 1}".encode()


class TestConfigChange:
    async def test_add_voter(self):
        c = Cluster(3)
        leader = c.elect()
        # create the new node joining as n3
        from bifromq_tpu.raft.node import RaftNode as RN
        nid = "n3"
        c.ids.append(nid)
        c.applied[nid] = []
        node = RN(nid, [nid], c.transport,
                  apply_cb=lambda e: c.applied[nid].append((e.index, e.data)),
                  restore_cb=lambda b: c.applied[nid].__setitem__(
                      slice(None), eval(b.decode())))
        node.voters = set()  # passive until the leader's config reaches it
        c.transport.register(node)
        c.nodes[nid] = node
        fut = leader.change_config([*(set(c.ids) - {nid}), nid])
        c.run_until(lambda: fut.done())
        await fut
        await c.propose(b"with4")
        c.run_until(lambda: b"with4" in [d for _, d in c.applied[nid]],
                    max_ticks=1000)

    async def test_remove_voter(self):
        c = Cluster(3)
        leader = c.elect()
        victim = next(nid for nid in c.ids if nid != leader.id)
        fut = leader.change_config([nid for nid in c.ids if nid != victim])
        c.run_until(lambda: fut.done())
        await fut
        assert victim not in leader.voters
        await c.propose(b"threeminusone")


class TestLeaderTransfer:
    async def test_transfer(self):
        c = Cluster(3)
        leader = c.elect()
        await c.propose(b"x")
        target = next(nid for nid in c.ids if nid != leader.id)
        old_term = leader.term
        leader.transfer_leadership(target)
        c.run_until(lambda: c.nodes[target].role == Role.LEADER)
        assert c.nodes[target].term > old_term
        assert leader.role != Role.LEADER


class TestReadIndexGating:
    async def test_read_index_waits_for_term_start_commit(self):
        # a fresh leader must not serve reads below prior-term commits
        c = Cluster(3)
        first = c.elect()
        fut = first.propose(b"X")
        c.run_until(lambda: fut.done())
        idx = await fut
        c.transport.kill(first.id)
        c.run_until(lambda: c.leader() is not None
                    and c.leader().id != first.id)
        new_leader = c.leader()
        rfut = new_leader.read_index()
        c.run_until(lambda: rfut.done())
        assert await rfut >= idx  # covers the prior-term committed write
