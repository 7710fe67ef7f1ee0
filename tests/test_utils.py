"""Unit tests for shared infrastructure (pkg/ parity)."""

import pytest

from dragonfly2_tpu.utils import idgen
from dragonfly2_tpu.utils.cache import TTLCache
from dragonfly2_tpu.utils.dag import (
    DAG,
    CycleError,
    EdgeAlreadyExistsError,
    VertexAlreadyExistsError,
    VertexNotFoundError,
)
from dragonfly2_tpu.utils.digest import (
    digest_string,
    parse_digest,
    sha256_from_bytes,
    sha256_from_strings,
    verify,
)
from dragonfly2_tpu.utils.kvstore import (
    KVStore,
    make_network_topology_key,
    make_probed_count_key,
    make_probes_key,
)


class TestIDGen:
    def test_task_id_deterministic(self):
        a = idgen.task_id_v1("https://example.com/blob")
        b = idgen.task_id_v1("https://example.com/blob")
        assert a == b and len(a) == 64

    def test_task_id_meta_changes_id(self):
        url = "https://example.com/blob"
        base = idgen.task_id_v1(url, idgen.URLMeta())
        tagged = idgen.task_id_v1(url, idgen.URLMeta(tag="t"))
        ranged = idgen.task_id_v1(url, idgen.URLMeta(range="0-1023"))
        assert base != tagged and base != ranged

    def test_parent_task_id_ignores_range(self):
        url = "https://example.com/blob"
        m1 = idgen.URLMeta(range="0-1023")
        m2 = idgen.URLMeta(range="1024-2047")
        assert idgen.parent_task_id_v1(url, m1) == idgen.parent_task_id_v1(url, m2)

    def test_filtered_query_params_do_not_change_id(self):
        a = idgen.task_id_v1(
            "https://e.com/b?sig=111&x=1", idgen.URLMeta(filter="sig")
        )
        b = idgen.task_id_v1(
            "https://e.com/b?sig=222&x=1", idgen.URLMeta(filter="sig")
        )
        assert a == b

    def test_host_and_peer_ids(self):
        assert idgen.host_id_v1("h", 80) == "h-80"
        assert idgen.host_id_v2("1.2.3.4", "h") == sha256_from_strings("1.2.3.4", "h")
        assert idgen.peer_id_v1("1.2.3.4").startswith("1.2.3.4-")
        assert idgen.seed_peer_id_v1("1.2.3.4").endswith("_Seed")
        assert idgen.gnn_model_id_v1("a", "b") != idgen.mlp_model_id_v1("a", "b")


class TestDigest:
    def test_roundtrip(self):
        d = digest_string("sha256", sha256_from_bytes(b"hello"))
        assert verify(b"hello", d)
        assert not verify(b"world", d)
        algo, val = parse_digest(d)
        assert algo == "sha256" and len(val) == 64

    def test_invalid(self):
        with pytest.raises(ValueError):
            parse_digest("nope")
        with pytest.raises(ValueError):
            digest_string("crc32", "x")


class TestDAG:
    def test_vertex_crud(self):
        g = DAG()
        g.add_vertex("a", 1)
        with pytest.raises(VertexAlreadyExistsError):
            g.add_vertex("a", 2)
        assert g.get_vertex("a").value == 1
        with pytest.raises(VertexNotFoundError):
            g.get_vertex("zz")
        g.delete_vertex("a")
        assert "a" not in g

    def test_cycle_prevention(self):
        g = DAG()
        for v in "abc":
            g.add_vertex(v, None)
        g.add_edge("a", "b")
        g.add_edge("b", "c")
        with pytest.raises(CycleError):
            g.add_edge("c", "a")
        with pytest.raises(CycleError):
            g.add_edge("a", "a")
        with pytest.raises(EdgeAlreadyExistsError):
            g.add_edge("a", "b")
        assert not g.can_add_edge("c", "a")
        assert g.can_add_edge("a", "c")

    def test_degrees_and_edge_deletion(self):
        g = DAG()
        for v in "abc":
            g.add_vertex(v, None)
        g.add_edge("a", "b")
        g.add_edge("a", "c")
        assert g.get_vertex("a").out_degree == 2
        assert g.get_vertex("b").in_degree == 1
        g.delete_vertex_in_edges("b")
        assert g.get_vertex("b").in_degree == 0
        assert g.get_vertex("a").out_degree == 1
        g.delete_vertex_out_edges("a")
        assert g.get_vertex("c").in_degree == 0

    def test_delete_vertex_cleans_edges(self):
        g = DAG()
        for v in "abc":
            g.add_vertex(v, None)
        g.add_edge("a", "b")
        g.add_edge("b", "c")
        g.delete_vertex("b")
        assert g.get_vertex("a").out_degree == 0
        assert g.get_vertex("c").in_degree == 0
        assert sorted(v.id for v in g.source_vertices()) == ["a", "c"]

    def test_descendants(self):
        g = DAG()
        for v in "abcd":
            g.add_vertex(v, None)
        g.add_edge("a", "b")
        g.add_edge("b", "c")
        assert set(g.descendants("a")) == {"b", "c"}


class TestTTLCache:
    def test_set_get_delete(self):
        c = TTLCache()
        c.set("k", 42)
        v, ok = c.get("k")
        assert ok and v == 42
        c.delete("k")
        assert c.get("k") == (None, False)

    def test_expiry(self, monkeypatch):
        import dragonfly2_tpu.utils.cache as cache_mod

        t = [100.0]
        monkeypatch.setattr(cache_mod.time, "monotonic", lambda: t[0])
        c = TTLCache(default_ttl=5.0)
        c.set("k", "v")
        assert c.get("k") == ("v", True)
        t[0] = 106.0
        assert c.get("k") == (None, False)
        c.set("p", "q", ttl=cache_mod.NO_EXPIRATION)
        t[0] = 1e9
        assert c.get("p") == ("q", True)


class TestKVStore:
    def test_hash_list_counter(self):
        kv = KVStore()
        key = make_network_topology_key("s", "d")
        kv.hset(key, {"averageRTT": 100, "createdAt": 1})
        assert kv.hget(key, "averageRTT") == 100
        assert kv.hgetall(key)["createdAt"] == 1

        q = make_probes_key("s", "d")
        for i in range(7):
            kv.rpush(q, i)
        assert kv.llen(q) == 7
        assert kv.lpop(q) == 0
        assert kv.lrange(q, 0, -1) == [1, 2, 3, 4, 5, 6]
        assert kv.lrange(q, 0, 2) == [1, 2, 3]

        c = make_probed_count_key("h")
        assert kv.incr(c) == 1
        assert kv.incr(c, 5) == 6

    def test_scan_and_delete(self):
        kv = KVStore()
        kv.hset(make_network_topology_key("a", "b"), {"x": 1})
        kv.hset(make_network_topology_key("a", "c"), {"x": 1})
        kv.hset(make_probes_key("a", "b"), {"x": 1})
        assert len(kv.scan_iter("networktopology:a:*")) == 2
        assert kv.delete(make_network_topology_key("a", "b")) == 1
        assert len(kv.scan_iter("networktopology:a:*")) == 1

    def test_expire(self, monkeypatch):
        import dragonfly2_tpu.utils.kvstore as kv_mod

        t = [0.0]
        monkeypatch.setattr(kv_mod.time, "monotonic", lambda: t[0])
        kv = KVStore()
        kv.set("k", "v")
        kv.expire("k", 10)
        assert kv.get("k") == "v"
        t[0] = 11.0
        assert kv.get("k") is None
        assert not kv.exists("k")


def test_cli_config_yaml_env_overrides(tmp_path, monkeypatch):
    """Service config precedence: defaults < YAML < env < explicit
    overrides; unknown keys fail loudly (cli/config.py)."""
    import pytest

    from dragonfly2_tpu.cli.config import ConfigError, load_config
    from dragonfly2_tpu.scheduler.server import SchedulerServerConfig

    p = tmp_path / "s.yaml"
    p.write_text("listen: 1.2.3.4:9\nretry_limit: 7\ntrain_interval: 10.5\n")
    cfg = load_config(SchedulerServerConfig, p)
    assert cfg.listen == "1.2.3.4:9" and cfg.retry_limit == 7
    assert cfg.train_interval == 10.5

    monkeypatch.setenv("DF_SCHEDULER_RETRY_LIMIT", "3")
    cfg = load_config(SchedulerServerConfig, p, env_prefix="DF_SCHEDULER")
    assert cfg.retry_limit == 3  # env beats yaml

    cfg = load_config(
        SchedulerServerConfig, p, env_prefix="DF_SCHEDULER", overrides={"retry_limit": 1}
    )
    assert cfg.retry_limit == 1  # explicit beats env

    p.write_text("no_such_key: 1\n")
    with pytest.raises(ConfigError):
        load_config(SchedulerServerConfig, p)


def test_example_configs_parse():
    """The shipped example YAMLs must stay loadable against the real
    config dataclasses."""
    import os

    from dragonfly2_tpu.cli.config import load_config
    from dragonfly2_tpu.client.daemon import DaemonConfig
    from dragonfly2_tpu.manager.server import ManagerServerConfig
    from dragonfly2_tpu.scheduler.server import SchedulerServerConfig
    from dragonfly2_tpu.trainer.server import TrainerServerConfig

    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "hack", "configs")
    load_config(SchedulerServerConfig, os.path.join(root, "scheduler.yaml"))
    load_config(ManagerServerConfig, os.path.join(root, "manager.yaml"))
    load_config(TrainerServerConfig, os.path.join(root, "trainer.yaml"))
    load_config(DaemonConfig, os.path.join(root, "daemon.yaml"))


def test_cli_config_null_override_rules():
    """Explicit null clears Optional fields but is rejected for typed
    non-optional fields (would crash later otherwise)."""
    import pytest

    from dragonfly2_tpu.cli.config import ConfigError, load_config
    from dragonfly2_tpu.scheduler.server import SchedulerServerConfig

    with pytest.raises(ConfigError, match="cannot be null"):
        load_config(SchedulerServerConfig, overrides={"retry_limit": None})
    with pytest.raises(ConfigError, match="cannot be null"):
        load_config(SchedulerServerConfig, overrides={"manager_address": None})


def test_example_configs_load_against_current_dataclasses():
    """hack/configs/*.yaml (shipped into the Docker image) must keep
    loading as the config dataclasses evolve — load_config rejects
    unknown keys loudly, so drift fails here instead of at deploy."""
    import glob
    import os

    from dragonfly2_tpu.cli.config import load_config
    from dragonfly2_tpu.client.daemon import DaemonConfig
    from dragonfly2_tpu.manager.server import ManagerServerConfig
    from dragonfly2_tpu.scheduler.server import SchedulerServerConfig
    from dragonfly2_tpu.trainer.server import TrainerServerConfig

    root = os.path.join(os.path.dirname(__file__), "..", "hack", "configs")
    classes = {
        "manager": ManagerServerConfig,
        "scheduler": SchedulerServerConfig,
        "trainer": TrainerServerConfig,
        "daemon": DaemonConfig,
    }
    seen = set()
    for path in sorted(glob.glob(os.path.join(root, "*.yaml"))):
        name = os.path.basename(path).split(".")[0]
        cls = classes[name]
        load_config(cls, path)  # raises on unknown/invalid keys
        seen.add(name)
    assert seen == set(classes), f"missing example configs: {set(classes) - seen}"


def test_deploy_manifests_set_keys_exist_on_dataclasses():
    """Every --set key in docker-compose and the k8s manifests must be a
    real field of the service's config dataclass (load_config rejects
    unknown keys at boot — catch the drift here, not in a cluster)."""
    import dataclasses
    import os
    import re

    import yaml

    from dragonfly2_tpu.client.daemon import DaemonConfig
    from dragonfly2_tpu.colocated import ColocatedConfig
    from dragonfly2_tpu.manager.server import ManagerServerConfig
    from dragonfly2_tpu.scheduler.server import SchedulerServerConfig
    from dragonfly2_tpu.trainer.server import TrainerServerConfig

    classes = {
        "manager": ManagerServerConfig,
        "scheduler": SchedulerServerConfig,
        "trainer": TrainerServerConfig,
        "colocated": ColocatedConfig,
        "daemon": DaemonConfig,
    }
    fields = {
        svc: {f.name for f in dataclasses.fields(cls)} for svc, cls in classes.items()
    }

    def check_args(svc: str, args: list):
        assert svc in fields, f"unknown service {svc!r}"
        for i, a in enumerate(args):
            if a == "--set":
                key = str(args[i + 1]).split("=", 1)[0]
                assert key in fields[svc], f"{svc}: unknown --set key {key!r}"

    root = os.path.join(os.path.dirname(__file__), "..")
    compose = yaml.safe_load(open(os.path.join(root, "deploy/docker-compose/docker-compose.yml")))
    for name, svc in compose["services"].items():
        cmd = svc.get("command") or []
        if cmd:
            check_args(cmd[0], cmd)

    for doc in yaml.safe_load_all(open(os.path.join(root, "deploy/kubernetes/manifests.yaml"))):
        if not doc or doc.get("kind") not in ("Deployment", "DaemonSet"):
            continue
        for c in doc["spec"]["template"]["spec"]["containers"]:
            args = c.get("args") or []
            if args:
                check_args(args[0], args)


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the helper touches no directory
    setting (jax reads the variable itself). Unset: one absolute path
    inside the checkout, the same on every call — the path is part of
    the cache key, so a directory that moves never hits."""
    import os

    import jax

    from dragonfly2_tpu.utils import jitcache

    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    try:
        jax.config.update("jax_compilation_cache_dir", "/placed/by/the/test")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
        assert jitcache.enable_compile_cache() == "/from/outside"
        assert jax.config.jax_compilation_cache_dir == "/placed/by/the/test"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first, second = jitcache.enable_compile_cache(), jitcache.enable_compile_cache()
        assert first == second == jax.config.jax_compilation_cache_dir
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(root, ".jax_cache")
        # every compile is stored, the sub-second bucket rungs included
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
