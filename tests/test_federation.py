"""GRU piece-sequence wiring + the merge a cadence ends with (SURVEY §7
stage 7): per-host rounds → the versions they fitted → example-weighted
merge → one uploaded global model."""

import numpy as np
import pytest

from dragonfly2_tpu.schema.columnar import write_csv
from dragonfly2_tpu.schema.features import extract_piece_sequences
from dragonfly2_tpu.schema.columnar import records_to_columns
from dragonfly2_tpu.schema.synth import make_download_records
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.train import FitConfig
from dragonfly2_tpu.trainer.training import Training, TrainingConfig
from dragonfly2_tpu.utils.idgen import host_id_v2


def test_extract_piece_sequences_shapes_and_labels():
    recs = make_download_records(40, seed=3)
    seqs = extract_piece_sequences(records_to_columns(recs))
    assert seqs.sequences.ndim == 3 and seqs.sequences.shape[2] == 2
    assert seqs.sequences.shape[0] == seqs.labels.shape[0] == seqs.lengths.shape[0]
    assert seqs.sequences.shape[0] > 0
    assert (seqs.lengths >= 1).all()
    assert np.isfinite(seqs.labels).all()
    # prefix features are log-costs: positive where within length
    for i in range(min(5, len(seqs.lengths))):
        L = seqs.lengths[i]
        assert (seqs.sequences[i, :L, 0] > 0).all()
        assert (seqs.sequences[i, L:, 0] == 0).all()


def _seed_storage(tmp_path, hosts):
    storage = TrainerStorage(tmp_path / "store")
    for i, (ip, hostname, n, seed) in enumerate(hosts):
        hid = host_id_v2(ip, hostname)
        p = tmp_path / f"part{i}.csv"
        write_csv(p, make_download_records(n, seed=seed))
        storage.append_download(hid, p.read_bytes())
    return storage


def test_gru_fit_through_training(tmp_path):
    storage = _seed_storage(tmp_path, [("10.0.0.1", "s1", 120, 1)])
    uploads = []

    class Mgr:
        def create_model(self, **kw):
            uploads.append(kw)

    cfg = TrainingConfig(
        mlp=FitConfig(batch_size=64, epochs=2),
        gru=True,
        min_topology_records=10**9,  # GNN leg intentionally below min
        streaming=False,
    )
    t = Training(storage, manager_client=Mgr(), config=cfg)
    outcome = t.train("10.0.0.1", "s1")
    assert outcome.gru_error is None, outcome.gru_error
    assert outcome.gru_metrics and "mse" in outcome.gru_metrics
    types = sorted(u["model_type"] for u in uploads)
    assert "gru" in types and "mlp" in types


def test_iter_download_chunks_matches_list(tmp_path):
    """The GRU leg's bounded-memory chunked read must see exactly the
    records list_download sees — including across the embedded headers
    that separate appended upload rounds."""
    storage = TrainerStorage(tmp_path / "store")
    hid = host_id_v2("10.0.0.1", "s1")
    for seed in (1, 2):  # two upload rounds → an embedded header
        p = tmp_path / f"round{seed}.csv"
        write_csv(p, make_download_records(30, seed=seed))
        storage.append_download(hid, p.read_bytes())
    full = storage.list_download(hid)
    chunks = list(storage.iter_download_chunks(hid, chunk_records=7))
    assert [len(c) for c in chunks] == [7] * 8 + [4]  # 60 records
    flat = [r for c in chunks for r in c]
    assert len(flat) == len(full) == 60
    assert [r.id for r in flat] == [r.id for r in full]


def test_gru_max_sequences_caps_the_fit(tmp_path, monkeypatch):
    """gru_max_sequences bounds what the GRU leg materializes — the fit
    sees at most the cap, and the NEWEST sequences win (in incremental
    mode the file is never cleared; an oldest-first cap would pin the
    model to stale history forever)."""
    import dragonfly2_tpu.trainer.train as T

    storage = _seed_storage(tmp_path, [("10.0.0.1", "s1", 120, 1)])
    all_seqs = extract_piece_sequences(
        records_to_columns(storage.list_download(host_id_v2("10.0.0.1", "s1")))
    )
    total = all_seqs.sequences.shape[0]
    assert total > 4  # the cap below actually bites

    fitted = {}
    real_train_gru = T.train_gru

    def spy(sequences, labels, **kw):
        fitted["n"] = sequences.shape[0]
        fitted["labels"] = np.array(labels)
        return real_train_gru(sequences, labels, **kw)

    monkeypatch.setattr(T, "train_gru", spy)
    uploads = []

    class Mgr:
        def create_model(self, **kw):
            uploads.append(kw)

    cfg = TrainingConfig(
        mlp=FitConfig(batch_size=64, epochs=2),
        gru=True,
        gru_min_sequences=1,
        gru_max_sequences=4,
        min_topology_records=10**9,
        streaming=False,
    )
    t = Training(storage, manager_client=Mgr(), config=cfg)
    outcome = t.train("10.0.0.1", "s1")
    assert outcome.gru_error is None, outcome.gru_error
    assert "gru" in {u["model_type"] for u in uploads}
    assert fitted["n"] == 4  # the cap, not the full dataset
    # newest-kept: the fitted labels are the TAIL of the full label
    # stream, not its head
    np.testing.assert_array_equal(fitted["labels"], all_seqs.labels[-4:])


class _Mgr:
    def __init__(self):
        self.uploads = []

    def create_model(self, **kw):
        self.uploads.append(kw)


def _leaves(tree) -> list:
    import jax

    return jax.tree_util.tree_leaves(tree)


def _weighted_mean(trees: list, weights: list) -> list:
    """Leaf by leaf, in float32: what one average is."""
    w = np.asarray(weights, np.float32) / np.float32(sum(weights))
    return [sum(leaf * wi for leaf, wi in zip(leaves, w)) for leaves in zip(*map(_leaves, trees))]


def test_a_cadence_merges_what_its_rounds_fitted(tmp_path):
    """Three hosts' rounds side by side: nine versions under the hosts'
    ids, then ONE merged MLP under the federated id, the pair-weighted
    float32 mean of the three MLP versions just registered. Nothing is
    fitted a second time and nothing is read from storage (each round
    cleared its upload)."""
    import threading

    hosts = [("10.0.0.1", "s1", 80, 1), ("10.0.0.2", "s2", 60, 2), ("10.0.0.3", "s3", 70, 3)]
    storage = _seed_storage(tmp_path, hosts)
    mgr = _Mgr()
    cfg = TrainingConfig(mlp=FitConfig(batch_size=64, epochs=3), min_topology_records=10**9, streaming=False)
    t = Training(storage, manager_client=mgr, config=cfg)
    fits = []
    import dragonfly2_tpu.trainer.training as training_mod

    real = training_mod.train_mlp

    def counted(features, labels, **kw):
        fits.append(features.shape[0])
        return real(features, labels, **kw)

    training_mod.train_mlp = counted
    try:
        threads = [threading.Thread(target=t.train, args=(ip, hostname)) for ip, hostname, _, _ in hosts]
        for k, th in enumerate(threads):
            th.start()
            while t.admission._arrivals <= k:
                pass
        for th in threads:
            th.join(300)
    finally:
        training_mod.train_mlp = real
    assert len(fits) == 3  # every upload fitted once
    assert storage.host_ids() == []  # and cleared: the merge had no record to read
    mlps = {u["hostname"]: u for u in mgr.uploads if u["model_type"] == "mlp"}
    assert set(mlps) == {"s1", "s2", "s3", "federated"}
    merged = mgr.uploads[-1]
    assert merged["hostname"] == "federated" and merged["ip"] == ""
    from dragonfly2_tpu.utils.idgen import federated_model_id_v1

    assert merged["model_id"] == federated_model_id_v1()
    # weighted by the pairs each was fitted on (each host's own, whatever order the fits began in),
    # in the hosts' order by id
    from dragonfly2_tpu.schema.features import extract_pair_features

    pairs = [extract_pair_features(records_to_columns(make_download_records(n, seed=seed))).features.shape[0] for _, _, n, seed in hosts]
    assert sorted(pairs) == sorted(fits) and len(set(pairs)) == 3
    fits = pairs
    by_host = {host_id_v2(ip, h): (mlps[h]["params"], n) for (ip, h, _, _), n in zip(hosts, fits)}
    order = sorted(by_host)
    want = _weighted_mean([by_host[h][0] for h in order], [by_host[h][1] for h in order])
    got = _leaves(merged["params"])
    assert got and all(isinstance(leaf, np.ndarray) and leaf.dtype == np.float32 for leaf in got)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)
    # scored on the merged parameters themselves: every host's holdout rows (so few that all are
    # kept), pooled, against a plain float32 forward
    from dragonfly2_tpu.trainer.serving import NumpyMLPScorer

    held_x, held_y = [], []
    for (ip, h, n, seed), _ in sorted(zip(hosts, fits), key=lambda hn: host_id_v2(hn[0][0], hn[0][1])):
        own = extract_pair_features(records_to_columns(make_download_records(n, seed=seed)))
        rows = np.random.default_rng(cfg.mlp.seed).permutation(len(own.labels))[: int(len(own.labels) * cfg.mlp.eval_fraction)]
        held_x.append(own.features[np.sort(rows)])
        held_y.append(own.labels[np.sort(rows)])
    err = NumpyMLPScorer(merged["params"]).predict(np.concatenate(held_x)) - np.concatenate(held_y)
    ev = merged["evaluation"]
    assert ev["hosts"] == 3.0 and ev["pairs"] == float(sum(fits)) and ev["holdout_rows"] == float(len(err)) > 0
    assert ev["mse"] == pytest.approx(float(np.mean(err**2)), rel=0.02)
    assert ev["mae"] == pytest.approx(float(np.mean(np.abs(err))), rel=0.02)
    assert t._fitted == {}  # the next cadence starts from nothing


def test_hosts_one_after_another_merge_when_a_second_has_fitted(tmp_path):
    """The trainer knows no calendar: a cadence ends when a round returns
    and none runs or waits. One host alone merges nothing and stays the
    newest; the next host's return finds two fitted since the last merge."""
    hosts = [("10.0.0.1", "s1", 80, 1), ("10.0.0.2", "s2", 60, 2), ("10.0.0.3", "s3", 70, 3)]
    storage = _seed_storage(tmp_path, hosts)
    mgr = _Mgr()
    cfg = TrainingConfig(mlp=FitConfig(batch_size=64, epochs=1), min_topology_records=10**9, gru=False, streaming=False)
    t = Training(storage, manager_client=mgr, config=cfg)
    merged_after = []
    for ip, hostname, _, _ in hosts:
        t.train(ip, hostname)
        merged_after.append(sum(u["hostname"] == "federated" for u in mgr.uploads))
    assert merged_after == [0, 1, 1]
    assert list(t._fitted) == [host_id_v2("10.0.0.3", "s3")]
    # a host that uploaded nothing is waited for by no one, and merges nothing
    assert t._merge() is None


@pytest.mark.parametrize(
    "weights,want",
    [([3, 1], 0.75), ([1, 1], 0.5), ([1, 3], 0.25)],
    ids=["first-heavier", "even", "second-heavier"],
)
def test_merge_versions_weighs_by_pairs(weights, want):
    from dragonfly2_tpu.trainer.federation import FittedVersion, merge_versions

    fitted = {
        "host-a": FittedVersion({"w": np.ones((2, 2), np.float32)}, weights[0]),
        "host-b": FittedVersion({"w": np.zeros((2, 2), np.float32)}, weights[1]),
    }
    merged, evaluation = merge_versions(fitted)
    np.testing.assert_allclose(merged["w"], want)
    assert merged["w"].dtype == np.float32
    # no host kept a holdout row: no error is stated, and none is stood in for
    assert evaluation == {"hosts": 2.0, "pairs": float(sum(weights)), "holdout_rows": 0.0}


def test_the_merged_model_is_scored_on_the_hosts_pooled_holdout_rows():
    """A merge worse than every host's own version says so: its error is
    the merged parameters' on the kept rows, not a mean of the hosts'."""
    from dragonfly2_tpu.trainer.federation import FittedVersion, holdout_sample, merge_versions

    def version(bias: float, pairs: int, seed: int) -> FittedVersion:
        x = np.random.default_rng(seed).normal(size=(pairs, 19)).astype(np.float32)
        y = np.full(pairs, bias, np.float32)  # what this host's own version predicts exactly
        params = {"layers": [{"w": np.zeros((19, 1), np.float32), "b": np.asarray([bias], np.float32)}]}
        return FittedVersion(params, pairs, holdout_sample(x, y, np.arange(pairs // 10)))

    merged, evaluation = merge_versions({"host-a": version(1.0, 3000, 1), "host-b": version(-1.0, 1000, 2)})
    # each host's own error is 0; the mean's bias is 0.5: off by 0.5 on a's rows and 1.5 on b's, 300 and 100 of them
    assert float(merged["layers"][0]["b"][0]) == pytest.approx(0.5)
    assert evaluation["holdout_rows"] == 400.0
    assert evaluation["mse"] == pytest.approx((300 * 0.25 + 100 * 2.25) / 400, rel=1e-3)


def test_a_holdout_sample_is_a_64th_and_no_fewer_than_1024():
    from dragonfly2_tpu.trainer.federation import holdout_sample

    x, y = np.arange(200_000, dtype=np.float32)[:, None], np.arange(200_000, dtype=np.float32)
    held = np.random.default_rng(0).permutation(200_000)
    for n_held, kept in ((0, 0), (10, 10), (1024, 1024), (65_535, 1024), (131_072, 2048)):
        sx, sy = holdout_sample(x, y, held[:n_held])
        assert len(sy) == kept and np.array_equal(sx[:, 0], sy)
        assert np.array_equal(sy, np.sort(held[:kept]).astype(np.float32))


def test_federated_merge_is_example_weighted():
    from dragonfly2_tpu.parallel.fedavg import fedavg_trees

    a = {"w": np.ones((2, 2), np.float32)}
    b = {"w": np.zeros((2, 2), np.float32)}
    merged = fedavg_trees([a, b], weights=[3.0, 1.0])
    np.testing.assert_allclose(np.asarray(merged["w"]), 0.75)


def test_merge_versions_of_nothing_raises():
    from dragonfly2_tpu.trainer.federation import merge_versions

    with pytest.raises(ValueError, match="no fitted version"):
        merge_versions({})


def test_fedavg_psum_on_mesh(mesh8):
    """In-mesh FedAvg over a `fed` axis: shard_map + psum averaging must
    match the host-side tree average."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    from dragonfly2_tpu.parallel.fedavg import fedavg_psum, fedavg_trees
    from dragonfly2_tpu.parallel.mesh import make_mesh

    n = 8
    mesh = make_mesh(jax.devices()[:n], fed=n)
    # per-replica params: replica i has value i; examples 1..8
    params = np.arange(n, dtype=np.float32).reshape(n, 1)
    examples = np.arange(1, n + 1, dtype=np.float32)

    def f(p, ex):
        return fedavg_psum({"w": p}, ex[0], axis_name="fed")["w"]

    out = shard_map(
        f,
        mesh=mesh,
        in_specs=(P("fed", None), P("fed")),
        out_specs=P("fed", None),
    )(params, examples)
    want = float(np.sum(params[:, 0] * examples) / examples.sum())
    np.testing.assert_allclose(np.asarray(out)[:, 0], want, rtol=1e-6)
