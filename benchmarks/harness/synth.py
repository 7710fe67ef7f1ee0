"""Seeded inputs for every cell: hosts, download records, the probe graph.

A copy of the program's ``schema/synth.py`` generators (sound, but seeded
at 0 and inside the program), driven from ``--seed``. The cost model a
parent's piece cost follows (load, idc, location) is the same, so the
fits have the same signal to learn.

The probe graph is new: a fixed number of distinct directed edges for
every seed (``hosts * fan_out * rounds``), so that the GNN's shapes, and
with them the compile cache, do not follow the seed.
"""

from __future__ import annotations

import numpy as np

from dragonfly2_tpu.schema import records as R

NS_PER_MS = 1e6

IDCS = ["idc-a", "idc-b", "idc-c", "idc-d"]
LOCS = [
    "as|cn|sh|dc1",
    "as|cn|sh|dc2",
    "as|cn|bj|dc1",
    "eu|de|fra|dc1",
    "na|us|iad|dc1",
]


def host_record(rng: np.random.Generator, hid: str, seed_peer: bool = False) -> R.HostRecord:
    uploads = int(rng.integers(0, 10_000))
    mem_total = 1 << 34
    mem_used_pct = float(rng.uniform(10, 95))
    return R.HostRecord(
        id=hid,
        type="super" if seed_peer else "normal",
        hostname=f"host-{hid[:12]}",
        ip=f"10.{rng.integers(0, 255)}.{rng.integers(0, 255)}.{rng.integers(1, 254)}",
        port=8002,
        download_port=8001,
        os="linux",
        concurrent_upload_limit=int(rng.integers(50, 200)),
        concurrent_upload_count=int(rng.integers(0, 50)),
        upload_count=uploads,
        upload_failed_count=int(rng.integers(0, max(uploads // 20, 1))),
        cpu=R.CPU(
            logical_count=8,
            percent=float(rng.uniform(0, 100)),
            process_percent=float(rng.uniform(0, 40)),
        ),
        memory=R.Memory(
            total=mem_total,
            used_percent=mem_used_pct,
            used=int(mem_total * mem_used_pct / 100.0),
            available=int(mem_total * (100.0 - mem_used_pct) / 100.0),
        ),
        network=R.Network(
            tcp_connection_count=int(rng.integers(10, 2000)),
            upload_tcp_connection_count=int(rng.integers(0, 500)),
            location=str(rng.choice(LOCS)),
            idc=str(rng.choice(IDCS)),
        ),
        disk=R.Disk(
            total=1 << 40,
            used_percent=float(rng.uniform(5, 90)),
            inodes_total=1 << 24,
            inodes_used_percent=float(rng.uniform(1, 60)),
        ),
    )


def download_records(n: int, seed: int, parents_per_record: int = 4) -> list:
    """``n`` download records, each with ``parents_per_record`` parents
    whose piece costs follow load, idc and location."""
    rng = np.random.default_rng([seed, 1])
    out = []
    for i in range(n):
        child = host_record(rng, f"child-{i}")
        total_pieces = int(rng.integers(8, 64))
        parents = []
        for p in range(parents_per_record):
            ph = host_record(rng, f"parent-{i}-{p}", seed_peer=bool(rng.random() < 0.2))
            base_ms = rng.uniform(5, 20)
            load = ph.cpu.percent / 100 + ph.concurrent_upload_count / max(
                ph.concurrent_upload_limit, 1
            )
            idc_penalty = 0.0 if ph.network.idc == child.network.idc else 30.0
            loc_shared = sum(
                1
                for a, b in zip(
                    ph.network.location.split("|"), child.network.location.split("|")
                )
                if a == b
            )
            mean_ms = base_ms * (1 + 2 * load) + idc_penalty + (4 - loc_shared) * 10
            pieces = [
                R.PieceRecord(
                    length=1 << 20,
                    cost=int(max(0.5, rng.normal(mean_ms, mean_ms * 0.1)) * NS_PER_MS),
                    created_at=i,
                )
                for _ in range(int(rng.integers(1, R.MAX_PIECES_PER_PARENT + 1)))
            ]
            parents.append(
                R.ParentRecord(
                    id=f"peer-parent-{i}-{p}",
                    state="Succeeded",
                    finished_piece_count=int(rng.integers(1, total_pieces + 1)),
                    upload_piece_count=len(pieces),
                    host=ph,
                    pieces=pieces,
                )
            )
        out.append(
            R.DownloadRecord(
                id=f"peer-child-{i}",
                state="Succeeded",
                cost=int(rng.integers(1, 60_000) * NS_PER_MS),
                finished_piece_count=total_pieces,
                task=R.TaskRecord(
                    id=f"task-{i % max(n // 4, 1)}",
                    url=f"https://origin.example.com/blob/{i}",
                    type="normal",
                    content_length=total_pieces << 20,
                    total_piece_count=total_pieces,
                    state="Succeeded",
                ),
                host=child,
                parents=parents,
            )
        )
    return out


def fleet(num_hosts: int, seed: int, seed_peers: int = 16) -> list:
    """The announced fleet: ``num_hosts`` host records, the first
    ``seed_peers`` of them seed peers."""
    rng = np.random.default_rng([seed, 2])
    return [
        host_record(rng, f"h{j:05d}-{seed % 100000:05d}", seed_peer=j < seed_peers)
        for j in range(num_hosts)
    ]


def probe_edges(
    num_hosts: int, seed: int, fan_out: int = 5, rounds: int = 2, seed_peers: int = 16
) -> list:
    """Directed probe edges ``(src, dst, rtt_ns)``: every host probes
    ``fan_out * rounds`` distinct targets, so the count of distinct
    directed edges is ``num_hosts * fan_out * rounds`` for every seed.

    Two of each host's targets are seed peers (every daemon probes the
    seed peers it downloads from, so they become the landmark hubs); the
    rest are ring offsets drawn from the seed, below ``num_hosts // 2``
    so that no pair is probed in both directions by the ring. RTT is a
    function of seeded latent coordinates plus jitter, as in the
    program's generator, so the GNN has geometry to learn."""
    rng = np.random.default_rng([seed, 3])
    per_host = fan_out * rounds
    hubs = 2
    coords = rng.uniform(0, 1, size=(num_hosts, 2))
    offsets = rng.choice(
        np.arange(1, num_hosts // 2), size=per_host - hubs, replace=False
    )
    edges = []
    for s in range(num_hosts):
        targets = [int((s + o) % num_hosts) for o in offsets]
        pool = [h for h in range(seed_peers) if h != s and h not in targets]
        targets += [int(t) for t in rng.choice(pool, size=hubs, replace=False)]
        for t in targets:
            dist = float(np.linalg.norm(coords[s] - coords[t]))
            rtt_ms = 1.0 + 80.0 * dist + rng.exponential(2.0)
            edges.append((s, t, int(rtt_ms * NS_PER_MS)))
    return edges


def topology_records(hosts: list, edges: list, dests_per_record: int = R.MAX_DEST_HOSTS) -> list:
    """The probe graph as NetworkTopology records, what the scheduler's
    snapshot task writes into the sink the announcer uploads."""
    by_src: dict[int, list] = {}
    for s, t, rtt in edges:
        by_src.setdefault(s, []).append((t, rtt))
    out = []
    for s, dests in by_src.items():
        sh = hosts[s]
        for k in range(0, len(dests), dests_per_record):
            out.append(
                R.NetworkTopologyRecord(
                    id=f"nt-{s}-{k}",
                    host=R.SrcHost(
                        id=sh.id, type=sh.type, hostname=sh.hostname, ip=sh.ip,
                        port=sh.port, network=sh.network,
                    ),
                    dest_hosts=[
                        R.DestHost(
                            id=hosts[t].id, type=hosts[t].type,
                            hostname=hosts[t].hostname, ip=hosts[t].ip,
                            port=hosts[t].port, network=hosts[t].network,
                            probes=R.ProbesRecord(average_rtt=rtt, created_at=len(out)),
                        )
                        for t, rtt in dests[k : k + dests_per_record]
                    ],
                    created_at=len(out),
                )
            )
    return out


def mlp_weights(seed: int, dims: list) -> dict:
    """He-normal float32 MLP weights at the published widths."""
    rng = np.random.default_rng([seed, 4])
    layers = []
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        layers.append(
            {
                "w": (rng.standard_normal((fan_in, fan_out)) * np.sqrt(2.0 / fan_in)).astype(
                    np.float32
                ),
                "b": (0.1 * rng.standard_normal(fan_out)).astype(np.float32),
            }
        )
    return {"layers": layers}


def gnn_weights(seed: int, num_nodes: int, node_features: int = 7, hidden: tuple = (64, 64),
                embed_dim: int = 16, head_hidden: int = 64, head_gain: float = 12.0) -> dict:
    """Seeded GraphSAGE weights at the published widths: a per-node
    embedding table, SAGE layers (self and neighbour weights) and the
    pairwise head over [h_src, h_dst, h_src*h_dst]. The embedding table
    is unit-scale and the head's first layer carries a gain, so that the
    predicted log-RTTs spread over about one unit as a fitted model's
    do: near-constant scores would leave the ranking nothing to decide."""
    rng = np.random.default_rng([seed, 7])
    d = node_features + embed_dim
    sage = []
    for h in hidden:
        scale = np.sqrt(2.0 / d)
        sage.append(
            {
                "w_self": (rng.standard_normal((d, h)) * scale).astype(np.float32),
                "w_nbr": (rng.standard_normal((d, h)) * scale).astype(np.float32),
                "b": (0.1 * rng.standard_normal(h)).astype(np.float32),
            }
        )
        d = h
    head = []
    for fan_in, fan_out in ((3 * d, head_hidden), (head_hidden, 1)):
        head.append(
            {
                "w": (
                    rng.standard_normal((fan_in, fan_out)) * np.sqrt(2.0 / fan_in) * head_gain
                ).astype(np.float32),
                "b": (0.1 * rng.standard_normal(fan_out)).astype(np.float32),
            }
        )
        head_gain = 1.0
    return {
        "sage": sage,
        "head": {"layers": head},
        "node_embed": rng.standard_normal((num_nodes, embed_dim)).astype(np.float32),
    }
