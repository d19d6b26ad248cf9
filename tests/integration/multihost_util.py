"""Shared rank-N member bootstrap for multi-host integration tests —
one copy of the subprocess template (env guards, JOINED handshake,
stdin keep-alive), used by test_multihost.py and the coordinator-mode
YAML sweep."""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MEMBER = """
import os, sys, time
sys.path.insert(0, {repo!r})
# fresh process: pin the CPU backend before anything imports jax
os.environ["JAX_PLATFORMS"] = "cpu"
from elasticsearch_tpu.cluster.bootstrap import MultiHostCluster
from elasticsearch_tpu.node import Node

node = Node(name={name!r}, data_path={data_path!r})
c = MultiHostCluster(node, rank={rank}, world={world}, transport_port={port},
                     master_host="127.0.0.1", ping_interval=0,
                     minimum_master_nodes=1)
ids = sorted(node.cluster_state.nodes)
assert len(ids) == {expect}, ids
assert node.cluster_state.master_node_id == ids[0], (
    node.cluster_state.master_node_id, ids)
assert not c.is_master
print("JOINED", flush=True)
line = sys.stdin.readline()  # wait for the test to release us
if "leave" in line:
    c.close()
    print("LEFT", flush=True)
"""


def member_code(port: int, rank: int = 1, world: int = 2,
                expect: int = 2, name: str = "rank1",
                data_path=None) -> str:
    return MEMBER.format(repo=REPO, port=port, rank=rank, world=world,
                         expect=expect, name=name, data_path=data_path)


def spawn_member(port: int, rank: int = 1, world: int = 2,
                 expect: int = 2, name: str = "rank1",
                 data_path=None) -> subprocess.Popen:
    """Spawn a member process and block until it has JOINED."""
    p = subprocess.Popen(
        [sys.executable, "-c",
         member_code(port, rank=rank, world=world, expect=expect,
                     name=name, data_path=data_path)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    line = p.stdout.readline()
    assert "JOINED" in line, line
    return p
