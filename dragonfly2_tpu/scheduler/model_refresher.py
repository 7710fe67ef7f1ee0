"""Model refresher: the last hop of the train→serve loop.

The reference designed — but never wired — the consumption side of its
model registry: the `ml` evaluator algorithm is a TODO that falls back to
the base score (reference scheduler/scheduling/evaluator/evaluator.go:53)
and would have called Triton ModelInfer against the model the manager
activates (reference manager/service/model.go:109). This component closes
that loop TPU-style: poll the manager for the *active* MLP model version,
download the weights once on version change, rebuild the in-process XLA
scorer, and install it into the running MLEvaluator. Any failure leaves
the previous scorer (or the base fallback) serving — a bad fit can never
poison scheduling, matching the reference's inactive-until-activated
state machine (manager/models/model.go:20-26).
"""

from __future__ import annotations

import threading

from dragonfly2_tpu.rpc import gen  # noqa: F401
import manager_pb2  # noqa: E402

from dragonfly2_tpu.scheduler import metrics as M
from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
from dragonfly2_tpu.trainer.serving import (
    BUCKET_LADDER,
    MLPScorer,
    bucket_rows,
    deserialize_params_auto,
)
from dragonfly2_tpu.utils import dflog, profiling

logger = dflog.get("scheduler.model_refresher")

# a GraphSAGE swap, by step: the whole of it (weights fetched, graph
# read and built, rows placed, embeddings computed, every rung warmed,
# the slot swapped), and inside it the walk of the engine's edges into
# records, the graph build from them, and the scorer's construction
# until its embeddings are ready on the chip
PH_GNN_INSTALL = profiling.phase_type("scheduler.gnn_install")
PH_GNN_EXPORT = profiling.phase_type("scheduler.gnn_export")
PH_GNN_GRAPH_BUILD = profiling.phase_type("scheduler.gnn_graph_build")
PH_GNN_EMBED = profiling.phase_type("scheduler.gnn_embed")
# the loaded version embedded again on a live graph that has moved (the
# three steps above run inside it, as inside an install), and the next
# capacity rung's embed and edge heads compiled ahead of the fleet
PH_GNN_REEMBED = profiling.phase_type("scheduler.gnn_reembed")
PH_GNN_RUNG_PREPARE = profiling.phase_type("scheduler.gnn_rung_prepare")


def _serving_rungs(serving) -> list[int]:
    """Every bucket rung a packed batch can reach: the ladder, plus the
    rung a batch lands on when its last request overshoots the service's
    pack target. A scorer is compiled at ALL of them before it is
    installed: a cold rung met on the serving thread stalls every queued
    decision behind an XLA compile for longer than the service's grace,
    and each of those decisions drops a rung on the ladder (seen on the
    chip, where one such compile takes over a second)."""
    top = serving.cfg.max_rows if serving is not None else BUCKET_LADDER[-1]
    return sorted({bucket_rows(n) for n in (*BUCKET_LADDER, 2 * top)})


class ModelRefresher:
    """Polls the manager model registry and installs the active MLP model
    into the evaluator; keeps serving the previous model on any error.

    With a :class:`~dragonfly2_tpu.scheduler.serving.ScoringService`
    attached, every install also hot-swaps the BATCHED serving slot
    (in-flight batches finish on the model they snapshotted — the
    service's swap contract): the active GNN occupies it when one is
    activated (embeddings computed here, at swap time, from the live
    probe graph), the MLP otherwise; the per-call MLP stays installed in
    the evaluator as the next rung down the degradation ladder."""

    def __init__(
        self,
        manager_client,
        evaluator: MLEvaluator,
        scheduler_cluster_id: int = 1,
        interval: float = 60.0,
        serving=None,  # scheduler.serving.ScoringService
        networktopology=None,  # probe-graph source for GNN embeddings
    ):
        self.manager = manager_client
        self.evaluator = evaluator
        self.cluster_id = scheduler_cluster_id
        self.interval = interval
        self.serving = serving
        self.networktopology = networktopology
        self.loaded_version: tuple[str, int] | None = None  # (model_id, version)
        self.loaded_gru_version: tuple[str, int] | None = None
        self.loaded_gnn_version: tuple[str, int] | None = None
        # the loaded GraphSAGE version's weights (a re-embed fetches
        # nothing), the graph version its embed read, and the node
        # capacities whose embed and edge heads are compiled
        self._gnn_params = None
        self._embedded_graph_version: int | None = None
        self._gnn_capacities: set[int] = set()
        # the installed per-call scorer, kept so a GNN withdrawal can
        # re-occupy the serving slot through the one install path
        self._mlp_scorer = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------------------
    def refresh_once(self) -> bool:
        """One poll round; returns True when a new model was installed."""
        try:
            resp = self.manager.ListModels(
                manager_pb2.ListModelsRequest(scheduler_cluster_id=self.cluster_id)
            )
        except Exception as e:
            logger.warning("model list poll failed: %s", e)
            return False

        # GRU + GNN refresh ride every poll, independent of MLP install
        # state (each is best-effort and never blocks the MLP)
        gru_installed = self._refresh_gru(resp)
        gnn_installed = self._refresh_gnn(resp)

        active = [
            m for m in resp.models if m.state == "active" and m.type == "mlp"
        ]
        if not active:
            # no active model → serve the base fallback (never uninstall a
            # model *on error*, but an explicit deactivation is an operator
            # decision and must take effect)
            if self.loaded_version is not None:
                logger.info("active model withdrawn; falling back to base evaluator")
                self.evaluator.set_model(None)
                self.loaded_version = None
                self._mlp_scorer = None
                if self.serving is not None and self.serving.model_kind() in (
                    "mlp",
                    "numpy",
                ):
                    self.serving.clear()
            return gru_installed or gnn_installed

        # newest ACTIVATION wins if several MLP models are active (e.g.
        # per-source-host model ids) — updated_at_ns is stamped by the
        # manager's activate flip, so re-activating an older model takes
        # effect; created_at_ns breaks ties for pre-migration rows
        m = max(active, key=lambda m: (m.updated_at_ns, m.created_at_ns))
        key = (m.model_id, m.version)
        if key == self.loaded_version:
            return gru_installed or gnn_installed

        try:
            w = self.manager.GetModelWeights(
                manager_pb2.GetModelRequest(model_id=m.model_id, version=m.version)
            )
            params = deserialize_params_auto(w.weights)
            scorer = MLPScorer(params)
            # compile + sanity-check before install: a scorer that cannot
            # run must never reach the scheduling hot path, and neither
            # may its first compile at any rung (plain or wave-ranked)
            import numpy as np

            from dragonfly2_tpu.schema.features import MLP_FEATURE_NAMES

            for rows in _serving_rungs(self.serving):
                x = np.zeros((rows, len(MLP_FEATURE_NAMES)), np.float32)
                scorer.predict(x)
                scorer.predict_ranked(x, np.zeros((rows,), np.int32))
        except Exception as e:
            logger.warning(
                "loading model %s v%d failed (%s); keeping previous", m.model_id, m.version, e
            )
            return gru_installed or gnn_installed

        self.evaluator.set_model(scorer)
        self.loaded_version = key
        self._mlp_scorer = scorer
        self._serve_mlp(scorer, key)
        logger.info("installed model %s v%d into ml evaluator", m.model_id, m.version)
        return True

    def _serve_mlp(self, scorer, key) -> None:
        """Hot-swap the batched serving slot to this MLP — unless a GNN
        holds it (the GNN is the higher rung; the per-call MLP installed
        above remains the fallback under it either way)."""
        if self.serving is None or self.serving.model_kind() == "gnn":
            return
        from dragonfly2_tpu.scheduler.serving import MLPServed

        self.serving.install(MLPServed(scorer), version=f"{key[0]}/v{key[1]}")

    def _refresh_gnn(self, resp) -> bool:
        """Install the newest active GNN as the batched serving model:
        weights from the registry, embeddings computed HERE (swap time)
        from the live probe graph and pinned on device next to the
        topology adjacency. Best-effort — a broken GNN (or a probe graph
        too small to embed) leaves the MLP serving and never blocks
        scheduling. Returns True when a GNN was (re)installed."""
        if self.serving is None:
            return False
        active = [m for m in resp.models if m.state == "active" and m.type == "gnn"]
        if not active:
            if self.loaded_gnn_version is not None:
                logger.info("active gnn withdrawn; serving falls back to mlp")
                self.loaded_gnn_version = None
                self._gnn_params = None
                if self.serving.model_kind() == "gnn":
                    self.serving.clear()
                    # re-occupy the slot with the loaded MLP, if any —
                    # through the one install path
                    if self.loaded_version is not None and self._mlp_scorer is not None:
                        self._serve_mlp(self._mlp_scorer, self.loaded_version)
            return False
        m = max(active, key=lambda m: (m.updated_at_ns, m.created_at_ns))
        key = (m.model_id, m.version)
        if key == self.loaded_gnn_version:
            return self._reembed_gnn(key)

        def fetch():
            w = self.manager.GetModelWeights(
                manager_pb2.GetModelRequest(model_id=m.model_id, version=m.version)
            )
            return deserialize_params_auto(w.weights)

        params = self._swap_in_gnn(key, fetch, PH_GNN_INSTALL, M.GNN_INSTALL_TOTAL, "installing")
        if params is None:
            return False
        self.loaded_gnn_version = key
        self._gnn_params = params
        return True

    def _reembed_gnn(self, key) -> bool:
        """The loaded version on the live graph as it is now, when that
        is no longer the graph its embed read: the same weights, placed
        by id and embedded again, every rung warmed, swapped in through
        the serving slot's one install path. Nothing begins when the
        graph has not moved, or when something else holds the slot."""
        moved = getattr(self.networktopology, "graph_version", None)
        if moved is None or self._gnn_params is None or self.serving.model_kind() != "gnn":
            return False
        now = moved()
        if now is None or now == self._embedded_graph_version:
            return False
        held = self._gnn_params
        return self._swap_in_gnn(key, lambda: held, PH_GNN_REEMBED, M.GNN_REEMBED_TOTAL, "embedding again") is not None

    def _swap_in_gnn(self, key, get_params, phase, counter, doing: str):
        """The one way a GraphSAGE scorer reaches the serving slot, for
        an install and a re-embed alike: inside ``phase``, the weights
        ``get_params()`` gives are built into a scorer on the live graph
        (rows by id, every rung warmed) and swapped in; ``counter``
        counts how it ended, the rows are counted, and the rung above is
        compiled ahead if it is due. Returns the weights, or None when
        nothing was swapped (the model in force stays)."""
        from dragonfly2_tpu.scheduler.serving import GNNServed

        try:
            with phase:
                params = get_params()
                scorer = self._build_gnn_scorer(params)
                if scorer is None:
                    counter.labels("skipped").inc()
                    return None
                self.serving.install(GNNServed(scorer), version=f"{key[0]}/v{key[1]}")
        except Exception as e:
            counter.labels("failed").inc()
            logger.warning(
                "%s gnn %s v%d failed (%s); keeping the serving model in force", doing, key[0], key[1], e
            )
            return None
        counter.labels("ok").inc()
        for row, count in scorer.rows.items():
            M.GNN_ROWS_TOTAL.labels(row).inc(count)
        logger.info(
            "%s gnn %s v%d as the batched serving model: done (rows %s)", doing, key[0], key[1], scorer.rows
        )
        self._prepare_next_rung(scorer)
        return params

    def _prepare_next_rung(self, scorer) -> None:
        """Once a scorer's graph is past the share of its node capacity
        at which the next is due, compile the embed and every rung's
        edge head for the doubled capacity: here, on the refresher's
        thread, after the swap and before the one that will need them,
        so a crossing compiles nothing anywhere."""
        from dragonfly2_tpu.trainer.serving import past_prepare_share

        cap = scorer.capacity
        if cap is None:
            return
        self._gnn_capacities.add(cap)
        if 2 * cap in self._gnn_capacities or not past_prepare_share(len(scorer.node_index()), cap):
            return
        try:
            with PH_GNN_RUNG_PREPARE:
                scorer.prepare_capacity(2 * cap, _serving_rungs(self.serving))
            self._gnn_capacities.add(2 * cap)
        except Exception as e:
            logger.warning("compiling the gnn for node capacity %d ahead failed (%s)", 2 * cap, e)

    def _build_gnn_scorer(self, params):
        """Probe graph → swap-time-embedded GNNScorer (None when the
        graph can't embed yet: no topology source or < 2 hosts). The
        live graph is not the one the version was fitted on: the scorer
        places the learned rows on it by host id."""
        if self.networktopology is None:
            logger.info("gnn active but no probe-graph source; not serving it")
            return None
        from dragonfly2_tpu.schema.columnar import records_to_columns
        from dragonfly2_tpu.schema.features import build_probe_graph
        from dragonfly2_tpu.trainer.serving import GNNScorer

        with PH_GNN_EXPORT:
            records = self.networktopology.export_records()
            # the version of the graph this export read: what a later
            # poll compares to learn that the fleet has moved on
            read = getattr(self.networktopology, "exported_version", None)
            self._embedded_graph_version = read() if read is not None else None
        with PH_GNN_GRAPH_BUILD:
            graph = build_probe_graph(records_to_columns(records)) if records else None
        if graph is None or graph.num_nodes < 2:
            logger.info("probe graph too small to embed; not serving the gnn")
            return None
        with PH_GNN_EMBED:
            scorer = GNNScorer(params, graph)
        # compile + sanity-check at swap time, like the MLP install
        for rows in _serving_rungs(self.serving):
            scorer.predict_rtt_log_ms(
                [graph.node_ids[0]] * rows, [graph.node_ids[1]] * rows
            )
        return scorer

    def _refresh_gru(self, resp) -> bool:
        """Install the newest active GRU alongside the MLP (model-based
        bad-node detection); best-effort — a broken GRU never blocks the
        MLP install or scheduling. Returns True when a GRU was
        (re)installed, so refresh_once's installed-something contract
        covers both model types."""
        if not hasattr(self.evaluator, "set_gru"):
            return False
        active = [m for m in resp.models if m.state == "active" and m.type == "gru"]
        if not active:
            if self.loaded_gru_version is not None:
                logger.info("active gru withdrawn; bad-node falls back to statistics")
                self.evaluator.set_gru(None)
                self.loaded_gru_version = None
            return False
        m = max(active, key=lambda m: (m.updated_at_ns, m.created_at_ns))
        key = (m.model_id, m.version)
        if key == self.loaded_gru_version:
            return False
        try:
            w = self.manager.GetModelWeights(
                manager_pb2.GetModelRequest(model_id=m.model_id, version=m.version)
            )
            from dragonfly2_tpu.trainer.serving import GRUScorer

            scorer = GRUScorer(deserialize_params_auto(w.weights))
            scorer.predict_next_log_cost([[5.0, 6.0, 7.0]])  # compile + sanity
        except Exception as e:
            logger.warning(
                "loading gru %s v%d failed (%s); keeping previous", m.model_id, m.version, e
            )
            return False
        self.evaluator.set_gru(scorer)
        self.loaded_gru_version = key
        logger.info("installed gru %s v%d for bad-node detection", m.model_id, m.version)
        return True

    # ------------------------------------------------------------------
    def start(self) -> None:
        self.refresh_once()
        self._thread = threading.Thread(
            target=self._loop, name="scheduler.model-refresher", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.refresh_once()
            except Exception:
                logger.exception("model refresh round failed")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
