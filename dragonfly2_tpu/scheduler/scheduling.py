"""Scheduling algorithm: assign candidate parents to downloading peers.

Semantics track the reference's v2 path (reference
scheduler/scheduling/scheduling.go:85-213 ScheduleCandidateParents,
:383-424 FindCandidateParents, :500-571 filterCandidateParents) — the
retry loop with back-to-source decisions, and the six filter rules:
blocklist, DAG-edge feasibility, same-host exclusion, bad-node, the
in-degree/seed "parent must itself be fed" rule, and free upload slots.

Decisions are pushed to the peer's stored stream handle (installed by the
RPC layer); responses are plain dataclasses so the algorithm is
transport-independent and testable in-process, the same way the reference
tests drive it against scripted mocks.
"""

# dfanalyze: hot — one schedule_candidate_parents call per peer decision

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from dragonfly2_tpu.scheduler.evaluator import Evaluator
from dragonfly2_tpu.scheduler.resource import (
    PEER_STATE_BACK_TO_SOURCE,
    PEER_STATE_RECEIVED_NORMAL,
    PEER_STATE_RUNNING,
    PEER_STATE_SUCCEEDED,
    HostType,
    Peer,
)
from dragonfly2_tpu.scheduler import metrics as M
from dragonfly2_tpu.scheduler import swarm
from dragonfly2_tpu.utils import dflog, faults, flight, profiling, tracing

logger = dflog.get("scheduling")

# dfprof phase ledger: the schedule op's wall split (whole decision vs
# the evaluator leg; the topology and storage legs are declared at
# their own sites) — live counters on /debug/prof, always on
PH_SCHEDULE = profiling.phase_type("scheduler.schedule_op")
# a decision's service from inside: find_candidate_parents whole, and
# within it the six rules and the evaluator's ranking (the rtt join and
# the scoring service's two round trips lie in evaluate, under phases of
# their own); find_parents - filter_parents - evaluate is the decision's
# self time
PH_FIND_PARENTS = profiling.phase_type("scheduler.find_parents")
PH_FILTER_PARENTS = profiling.phase_type("scheduler.filter_parents", inner=True)
PH_EVALUATE = profiling.phase_type("scheduler.evaluate", inner=True)

# What the process around the scheduler was doing when a decision began:
# a callable that names a stretch, given by whoever assembles a process in
# which that can be known (colocated/server.py: the stretches of the
# trainer's round on the same interpreter and chip) and imported by no
# one here. A scheduler alone has none and books nothing by stretch. The
# decision's seconds (find_parents' own, no second clock) go to the phase
# of the stretch it began in, so the counts of these sum to find_parents'
# and each mean is the service beside that stretch. One for the process,
# like the rest of what colocated.settle() takes on (the switch interval,
# the frozen collector): its stop() puts it back
STRETCHES = ("walk", "assemble", "fit_shared", "fit_alone", "idle")
PH_FIND_PARENTS_BESIDE = {
    stretch: profiling.phase_type(f"scheduler.find_parents_beside_{stretch}") for stretch in STRETCHES
}
stretch_provider = None  # () -> one of STRETCHES

# flight-recorder emitters: one event per scheduling decision, always on
# (the per-decision record the sampled trace usually misses)
EV_SCHEDULE = flight.event_type("scheduler.schedule")
EV_BACK_TO_SOURCE = flight.event_type("scheduler.schedule_back_to_source")
EV_SCHEDULE_FAILED = flight.event_type("scheduler.schedule_failed")

# fault point: one scheduling decision — chaos schedules inject latency
# (a wedged scheduler) or errors here; single predicate when disarmed
FP_SCHEDULE = faults.point("scheduler.schedule")

# defaults (reference scheduler/config/constants.go)
DEFAULT_RETRY_LIMIT = 5
DEFAULT_RETRY_BACK_TO_SOURCE_LIMIT = 3
DEFAULT_RETRY_INTERVAL = 0.05
DEFAULT_FILTER_PARENT_LIMIT = 15
DEFAULT_CANDIDATE_PARENT_LIMIT = 4


@dataclass
class SchedulingConfig:
    retry_limit: int = DEFAULT_RETRY_LIMIT
    retry_back_to_source_limit: int = DEFAULT_RETRY_BACK_TO_SOURCE_LIMIT
    retry_interval: float = DEFAULT_RETRY_INTERVAL
    filter_parent_limit: int = DEFAULT_FILTER_PARENT_LIMIT
    candidate_parent_limit: int = DEFAULT_CANDIDATE_PARENT_LIMIT


# -- responses pushed to the peer's stream ----------------------------------


@dataclass
class NormalTaskResponse:
    candidate_parents: list[Peer]


@dataclass
class NeedBackToSourceResponse:
    description: str


class SchedulingError(Exception):
    pass


class Scheduling:
    def __init__(
        self,
        evaluator: Evaluator,
        config: SchedulingConfig | None = None,
        dynconfig=None,  # optional provider of live candidate/filter limits
        seed_client=None,  # optional resource.seed_peer.SeedPeerClient
    ):
        self.evaluator = evaluator
        self.config = config or SchedulingConfig()
        self.dynconfig = dynconfig
        self.seed_client = seed_client

    # -- limits (dynconfig-overridable, reference scheduling.go:405-413) --
    def _candidate_parent_limit(self) -> int:
        if self.dynconfig is not None:
            v = getattr(self.dynconfig, "candidate_parent_limit", 0)
            if v and v > 0:
                return int(v)
        return self.config.candidate_parent_limit

    def _filter_parent_limit(self) -> int:
        if self.dynconfig is not None:
            v = getattr(self.dynconfig, "filter_parent_limit", 0)
            if v and v > 0:
                return int(v)
        return self.config.filter_parent_limit

    # -- v2 entrypoint ----------------------------------------------------
    def schedule_candidate_parents(
        self, peer: Peer, blocklist: set[str] | None = None, cancelled=None
    ) -> None:
        """Retry loop: find candidates and push NormalTaskResponse, or
        decide back-to-source (peer demand or retry exhaustion) and push
        NeedBackToSourceResponse. Raises SchedulingError when the retry
        limit is exhausted and back-to-source isn't possible."""
        blocklist = blocklist or set()
        n = 0
        FP_SCHEDULE()
        _t0 = time.perf_counter()
        # the per-schedule span only exists when something will record
        # it: the unsampled/disabled path (is_sampling False — this IS
        # the hot path when no collector is drinking) pays a predicate
        # and no-op calls
        if tracing.is_sampling():
            _span = tracing.get("scheduler").start_span(
                "schedule", peer_id=peer.id, task_id=peer.task.id
            )
            _cm = tracing.use_span(_span)
        else:
            _span = tracing.NOOP_SPAN
            _cm = tracing.noop_cm()
        M.CONCURRENT_SCHEDULE_GAUGE.inc()
        try:
            # active while the loop runs so evaluator/topology child
            # spans parent under the scheduling decision automatically
            with _cm:
                self._schedule_loop(peer, blocklist, cancelled, n, _t0, _span)
        except BaseException:
            _span.end("error")
            raise
        finally:
            M.CONCURRENT_SCHEDULE_GAUGE.dec()
            _span.end("ok")  # idempotent; attributes set at decision points
            # observe-only off the existing timer (one ~0.6µs ledger
            # add, no enter bookkeeping): concurrency is already
            # visible via CONCURRENT_SCHEDULE_GAUGE
            PH_SCHEDULE.observe(time.perf_counter() - _t0)

    def _schedule_loop(self, peer, blocklist, cancelled, n, _t0, _span):
        while True:
            if cancelled is not None and cancelled():
                return

            # while a seed download is in flight for this task, don't send
            # the child to the origin and don't burn its retry budget — the
            # whole point of the seed is that origin traffic happens once
            seeding = (
                self.seed_client is not None
                and self.seed_client.is_inflight(peer.task.id)
            )

            # explicit demand wins even while seeding — the demanding peer
            # IS the seed (its registration carries need_back_to_source)
            if peer.need_back_to_source and peer.task.can_back_to_source():
                _span.set(back_to_source="peer demand", retries=n)
                EV_BACK_TO_SOURCE(
                    peer_id=peer.id, task_id=peer.task.id,
                    reason="peer demand", retries=n,
                )
                self._send(
                    peer,
                    NeedBackToSourceResponse("peer's NeedBackToSource is true"),
                )
                return

            if not seeding and peer.task.can_back_to_source():
                if n >= self.config.retry_back_to_source_limit:
                    _span.set(back_to_source="retry limit", retries=n)
                    EV_BACK_TO_SOURCE(
                        peer_id=peer.id, task_id=peer.task.id,
                        reason="retry limit", retries=n,
                    )
                    self._send(
                        peer,
                        NeedBackToSourceResponse(
                            "scheduling exceeded RetryBackToSourceLimit"
                        ),
                    )
                    return

            if not seeding and n >= self.config.retry_limit:
                EV_SCHEDULE_FAILED(
                    peer_id=peer.id, task_id=peer.task.id, retries=n,
                    reason="retry limit exhausted",
                )
                raise SchedulingError(
                    f"scheduling exceeded RetryLimit {self.config.retry_limit}"
                )

            # re-schedule from a clean slate: drop existing parent edges
            peer.task.delete_peer_in_edges(peer.id)
            swarm.on_reschedule(peer.task.id, peer.id)

            candidate_parents, found = self.find_candidate_parents(peer, blocklist)
            if not found:
                if n == 0 and self.seed_client is not None:
                    # cold task with no feedable parents: ask a seed peer
                    # to fetch it (reference seed_peer.go:92-213 trigger);
                    # the retry loop then finds the seed as first parent.
                    # The full UrlMeta rides along — filter/range are part
                    # of the task id, so dropping them would make the seed
                    # register a different task entirely
                    task = peer.task
                    self.seed_client.trigger(
                        task.id,
                        task.url,
                        tag=task.tag,
                        application=task.application,
                        digest=task.digest,
                        url_filter="&".join(task.filters),
                        url_range=task.url_range,
                    )
                n += 1
                time.sleep(self.config.retry_interval)
                continue

            M.SCHEDULE_DURATION.observe(time.perf_counter() - _t0)
            _span.set(candidates=len(candidate_parents), retries=n).end("ok")
            EV_SCHEDULE(
                peer_id=peer.id,
                task_id=peer.task.id,
                retries=n,
                parent_ids=[p.id for p in candidate_parents],
            )
            self._send(peer, NormalTaskResponse(candidate_parents))

            for parent in candidate_parents:
                try:
                    peer.task.add_peer_edge(parent, peer)
                except Exception as e:
                    logger.warning("peer %s add edge failed: %s", peer.id, e)
            # the first ranked candidate is the decision's primary
            # parent — the tree edge the swarm observatory tracks
            swarm.on_primary_parent(
                peer.task.id, peer.id, candidate_parents[0].id
            )
            return

    # -- finders ----------------------------------------------------------
    def find_candidate_parents(
        self, peer: Peer, blocklist: set[str] | None = None
    ) -> tuple[list[Peer], bool]:
        # the stretch is read once, as the decision begins
        provider = stretch_provider
        stretch = provider() if provider is not None else None
        try:
            with PH_FIND_PARENTS:
                # only ReceivedNormal/Running peers reschedule; other states
                # (incl. BackToSource) are already placed
                if not peer.fsm.is_state(PEER_STATE_RECEIVED_NORMAL, PEER_STATE_RUNNING):
                    return [], False

                with PH_FILTER_PARENTS:
                    candidates = self._filter_candidate_parents(peer, blocklist or set())
                if not candidates:
                    return [], False

                total = peer.task.total_piece_count
                # duplicated call instead of maybe_span: the unsampled branch
                # then pays ONE predicate — not even the attrs dict build
                with PH_EVALUATE:
                    if tracing.is_sampling():
                        with tracing.get("scheduler").span("evaluate", candidates=len(candidates)):
                            candidates = self.evaluator.evaluate_parents(candidates, peer, total)
                    else:
                        candidates = self.evaluator.evaluate_parents(candidates, peer, total)
                limit = self._candidate_parent_limit()
                return candidates[:limit], True
        finally:
            # the same seconds, whatever way the decision ended; a name
            # outside STRETCHES books nothing
            beside = PH_FIND_PARENTS_BESIDE.get(stretch)
            if beside is not None:
                beside.observe(PH_FIND_PARENTS.last_s)

    def find_success_parent(
        self, peer: Peer, blocklist: set[str] | None = None
    ) -> Peer | None:
        if not peer.fsm.is_state(PEER_STATE_RUNNING):
            return None
        candidates = self._filter_candidate_parents(peer, blocklist or set())
        succeeded = [c for c in candidates if c.fsm.is_state(PEER_STATE_SUCCEEDED)]
        if not succeeded:
            return None
        total = peer.task.total_piece_count
        return self.evaluator.evaluate_parents(succeeded, peer, total)[0]

    def _filter_candidate_parents(self, peer: Peer, blocklist: set[str]) -> list[Peer]:
        """The six filter rules (reference scheduling.go:500-571)."""
        out = []
        for cand in peer.task.load_random_peers(self._filter_parent_limit()):
            if cand.id in blocklist:
                continue
            # peer-side blocks (reported bad parents) are also respected
            if cand.id in peer.block_parents:
                continue
            if not peer.task.can_add_peer_edge(cand.id, peer.id):
                continue
            # two daemons on one host would download from each other
            if peer.host.id == cand.host.id:
                continue
            if self.evaluator.is_bad_node(cand):
                continue
            try:
                in_degree = peer.task.peer_in_degree(cand.id)
            except Exception:
                continue
            # a normal-host parent must itself be fed: have a parent, or be
            # back-to-source, or have finished
            if (
                cand.host.type is HostType.NORMAL
                and in_degree == 0
                and not cand.fsm.is_state(PEER_STATE_BACK_TO_SOURCE)
                and not cand.fsm.is_state(PEER_STATE_SUCCEEDED)
            ):
                continue
            if cand.host.free_upload_count() <= 0:
                continue
            out.append(cand)
        return out

    @staticmethod
    def _send(peer: Peer, response) -> None:
        M.SCHEDULE_TOTAL.labels(
            "parents" if isinstance(response, NormalTaskResponse) else "back_to_source"
        ).inc()
        stream = peer.load_stream()
        if stream is None:
            raise SchedulingError(f"peer {peer.id}: load stream failed")
        stream.send(response)
