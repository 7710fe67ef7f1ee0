"""Hand-written gRPC method glue.

grpc_tools (the python protoc plugin) isn't in this image, so service
stubs are declared here as method tables: each service maps method name →
(kind, request type, response type). Clients get real
``channel.unary_unary``/``stream_stream`` callables; servers register
generic RPC handlers — byte-identical on the wire to plugin-generated
code (role parity: reference pkg/rpc client/server glue).
"""

# dfanalyze: hot — _instrument/_instrument_client wrap every RPC

from __future__ import annotations

import bisect
import hashlib
import time
import threading
from concurrent import futures
from dataclasses import dataclass
from typing import Any, Callable

import grpc

from dragonfly2_tpu.rpc import gen  # noqa: F401 — sets up flat imports
import common_pb2  # noqa: E402
import dfdaemon_pb2  # noqa: E402
import diagnose_pb2  # noqa: E402
import manager_pb2  # noqa: E402
import scheduler_pb2  # noqa: E402
import scheduler_v1_pb2  # noqa: E402
import telemetry_pb2  # noqa: E402
import topology_pb2  # noqa: E402
import trainer_pb2  # noqa: E402

# resilience imports only grpc + utils (never this module), so the
# module-scope import is cycle-free; it used to be re-imported inside
# every server handler invocation, which is exactly the per-call tax
# dfanalyze's hygiene pass now fails
from dragonfly2_tpu.rpc import resilience
from dragonfly2_tpu.utils import dflog, tracing
from dragonfly2_tpu.utils.metrics import default_registry as _registry

# Canonical service names — every client/server refers to these, so a
# rename can never leave a client dialing a service no server registers.
SCHEDULER_SERVICE = "dragonfly2_tpu.scheduler.Scheduler"
SCHEDULER_V1_SERVICE = "dragonfly2_tpu.scheduler.v1.SchedulerV1"
TOPOLOGY_SERVICE = "dragonfly2_tpu.topology.Topology"
TRAINER_SERVICE = "dragonfly2_tpu.trainer.Trainer"
MANAGER_SERVICE = "dragonfly2_tpu.manager.Manager"
DFDAEMON_SERVICE = "dragonfly2_tpu.dfdaemon.Dfdaemon"
# flight-recorder snapshots (utils/flight); every server assembly binds
# it so any live process can explain itself without restarting
DIAGNOSE_SERVICE = "dragonfly2_tpu.diagnose.Diagnose"
# cluster telemetry plane (docs/telemetry.md): services push metric
# snapshots to the manager over the channel they already hold
TELEMETRY_SERVICE = "dragonfly2_tpu.telemetry.Telemetry"

UNARY = "unary_unary"
UNARY_STREAM = "unary_stream"
STREAM_UNARY = "stream_unary"
STREAM_STREAM = "stream_stream"


@dataclass(frozen=True)
class Method:
    kind: str
    request: Any
    response: Any


SERVICES: dict[str, dict[str, Method]] = {
    SCHEDULER_SERVICE: {
        "AnnouncePeer": Method(
            STREAM_STREAM,
            scheduler_pb2.AnnouncePeerRequest,
            scheduler_pb2.AnnouncePeerResponse,
        ),
        "StatPeer": Method(UNARY, scheduler_pb2.StatPeerRequest, scheduler_pb2.PeerStat),
        "LeavePeer": Method(UNARY, scheduler_pb2.LeavePeerRequest, scheduler_pb2.Empty),
        "StatTask": Method(UNARY, scheduler_pb2.StatTaskRequest, scheduler_pb2.TaskStat),
        "AnnounceHost": Method(UNARY, scheduler_pb2.AnnounceHostRequest, scheduler_pb2.Empty),
        "LeaveHost": Method(UNARY, scheduler_pb2.LeaveHostRequest, scheduler_pb2.Empty),
        "AnnounceTask": Method(UNARY, scheduler_pb2.AnnounceTaskRequest, scheduler_pb2.Empty),
        "SyncProbes": Method(
            STREAM_STREAM,
            scheduler_pb2.SyncProbesRequest,
            scheduler_pb2.SyncProbesResponse,
        ),
    },
    SCHEDULER_V1_SERVICE: {
        "RegisterPeerTask": Method(
            UNARY, scheduler_v1_pb2.PeerTaskRequest, scheduler_v1_pb2.RegisterResult
        ),
        "ReportPieceResult": Method(
            STREAM_STREAM, scheduler_v1_pb2.PieceResult, scheduler_v1_pb2.PeerPacket
        ),
        "ReportPeerResult": Method(
            UNARY, scheduler_v1_pb2.PeerResult, scheduler_v1_pb2.Empty
        ),
        "StatTask": Method(UNARY, scheduler_v1_pb2.StatTaskRequest, scheduler_v1_pb2.Task),
        "LeaveTask": Method(UNARY, scheduler_v1_pb2.PeerTarget, scheduler_v1_pb2.Empty),
        "LeaveHost": Method(
            UNARY, scheduler_v1_pb2.LeaveHostRequest, scheduler_v1_pb2.Empty
        ),
        "AnnounceHost": Method(
            UNARY, scheduler_v1_pb2.AnnounceHostRequest, scheduler_v1_pb2.Empty
        ),
        "AnnounceTask": Method(
            UNARY, scheduler_v1_pb2.AnnounceTaskRequest, scheduler_v1_pb2.Empty
        ),
        "SyncProbes": Method(
            STREAM_STREAM,
            scheduler_v1_pb2.SyncProbesRequest,
            scheduler_v1_pb2.SyncProbesResponse,
        ),
    },
    TOPOLOGY_SERVICE: {
        "EstRtt": Method(
            UNARY, topology_pb2.EstRttRequest, topology_pb2.EstRttResponse
        ),
        "Neighbors": Method(
            UNARY, topology_pb2.NeighborsRequest, topology_pb2.NeighborsResponse
        ),
        "Stats": Method(UNARY, topology_pb2.StatsRequest, topology_pb2.StatsResponse),
    },
    TRAINER_SERVICE: {
        "Train": Method(STREAM_UNARY, trainer_pb2.TrainRequest, trainer_pb2.TrainResponse),
        "Capabilities": Method(
            UNARY,
            trainer_pb2.CapabilitiesRequest,
            trainer_pb2.CapabilitiesResponse,
        ),
    },
    MANAGER_SERVICE: {
        "GetScheduler": Method(UNARY, manager_pb2.GetSchedulerRequest, manager_pb2.Scheduler),
        "ListSchedulers": Method(
            UNARY, manager_pb2.ListSchedulersRequest, manager_pb2.ListSchedulersResponse
        ),
        "UpdateScheduler": Method(
            UNARY, manager_pb2.UpdateSchedulerRequest, manager_pb2.Scheduler
        ),
        "UpdateSeedPeer": Method(UNARY, manager_pb2.UpdateSeedPeerRequest, manager_pb2.SeedPeer),
        "KeepAlive": Method(STREAM_UNARY, manager_pb2.KeepAliveRequest, manager_pb2.Empty),
        "GetSchedulerClusterConfig": Method(
            UNARY,
            manager_pb2.GetSchedulerClusterConfigRequest,
            manager_pb2.SchedulerClusterConfig,
        ),
        "CreateJob": Method(UNARY, manager_pb2.CreateJobRequest, manager_pb2.Job),
        "GetJob": Method(UNARY, manager_pb2.GetJobRequest, manager_pb2.Job),
        "ListPendingJobs": Method(
            UNARY, manager_pb2.ListPendingJobsRequest, manager_pb2.ListPendingJobsResponse
        ),
        "UpdateJobResult": Method(
            UNARY, manager_pb2.UpdateJobResultRequest, manager_pb2.Job
        ),
        "CreateModel": Method(UNARY, manager_pb2.CreateModelRequest, manager_pb2.Model),
        "GetModel": Method(UNARY, manager_pb2.GetModelRequest, manager_pb2.Model),
        "GetModelWeights": Method(
            UNARY, manager_pb2.GetModelRequest, manager_pb2.ModelWeights
        ),
        "ListModels": Method(UNARY, manager_pb2.ListModelsRequest, manager_pb2.ListModelsResponse),
        "UpdateModel": Method(UNARY, manager_pb2.UpdateModelRequest, manager_pb2.Model),
        "IssueCertificate": Method(
            UNARY, manager_pb2.CertificateRequest, manager_pb2.CertificateResponse
        ),
    },
    DIAGNOSE_SERVICE: {
        "Diagnose": Method(
            UNARY, diagnose_pb2.DiagnoseRequest, diagnose_pb2.DiagnoseResponse
        ),
    },
    TELEMETRY_SERVICE: {
        "ReportTelemetry": Method(
            UNARY, telemetry_pb2.TelemetryReport, telemetry_pb2.TelemetryAck
        ),
    },
    DFDAEMON_SERVICE: {
        "Download": Method(
            UNARY_STREAM, dfdaemon_pb2.DownloadRequest, dfdaemon_pb2.DownloadResult
        ),
        "GetPieceTasks": Method(UNARY, dfdaemon_pb2.PieceTaskRequest, dfdaemon_pb2.PiecePacket),
        "SyncPieceTasks": Method(
            STREAM_STREAM, dfdaemon_pb2.PieceTaskRequest, dfdaemon_pb2.PiecePacket
        ),
        "StatTask": Method(UNARY, dfdaemon_pb2.StatTaskRequest, dfdaemon_pb2.Empty),
        "ImportTask": Method(UNARY, dfdaemon_pb2.ImportTaskRequest, dfdaemon_pb2.Empty),
        "ExportTask": Method(UNARY, dfdaemon_pb2.ExportTaskRequest, dfdaemon_pb2.Empty),
        "DeleteTask": Method(UNARY, dfdaemon_pb2.DeleteTaskRequest, dfdaemon_pb2.Empty),
    },
}


class ServiceClient:
    """Callable stubs for one service over one channel:
    ``client.AnnouncePeer(iter_of_requests)`` etc. Every method is
    wrapped with client-side observability (reference: otelgrpc +
    grpc-prometheus CLIENT interceptors, pkg/rpc/interceptor.go): a
    ``traceparent`` header carrying the caller's current span rides the
    invocation metadata, and outcomes land in the
    ``rpc_client_handled_total``/``rpc_client_handling_seconds``
    series — and with the resilience policy layer (rpc/resilience.py):
    per-service deadlines with downstream budget propagation, jittered
    capped retries under a token budget, and a per-target circuit
    breaker. ``target`` labels the breaker/budget (pass the dialed
    address when known — SchedulerSelector does); it defaults to the
    service's short name so single-target clients still get a breaker."""

    def __init__(self, channel: grpc.Channel, service: str, target: str = ""):
        methods = SERVICES[service]
        target = target or service.rsplit(".", 1)[-1]
        for name, m in methods.items():
            factory = getattr(channel, m.kind)
            callable_ = factory(
                f"/{service}/{name}",
                request_serializer=m.request.SerializeToString,
                response_deserializer=m.response.FromString,
            )
            setattr(
                self,
                name,
                resilience.wrap_call(
                    service,
                    name,
                    m.kind,
                    target,
                    _instrument_client(service, name, m.kind, callable_),
                ),
            )


# Per-RPC server observability (reference: every server wires
# grpc-prometheus + otelgrpc interceptors, pkg/rpc/interceptor.go).
# Counters/latency land in the shared default_registry so each service
# process's /metrics endpoint exposes them alongside its own series.
def _rpc_metrics():
    global _RPC_HANDLED, _RPC_LATENCY
    if _RPC_HANDLED is None:
        r = _registry
        _RPC_HANDLED = r.counter(
            "rpc_server_handled_total",
            "RPCs completed on the server, by outcome code",
            ("service", "method", "code"),
        )
        _RPC_LATENCY = r.histogram(
            "rpc_server_handling_seconds",
            "Server-side RPC handling latency (streams: until exhausted)",
            ("service", "method"),
        )
    return _RPC_HANDLED, _RPC_LATENCY


_RPC_HANDLED = None
_RPC_LATENCY = None


# Client-side twins of the server series (today only the server side is
# instrumented in the reference-parity set; the client series close the
# loop so a call that never reaches a server still lands somewhere).
def _rpc_client_metrics():
    global _RPC_CLIENT_HANDLED, _RPC_CLIENT_LATENCY
    if _RPC_CLIENT_HANDLED is None:
        r = _registry
        _RPC_CLIENT_HANDLED = r.counter(
            "rpc_client_handled_total",
            "RPCs completed on the client, by outcome code",
            ("service", "method", "code"),
        )
        _RPC_CLIENT_LATENCY = r.histogram(
            "rpc_client_handling_seconds",
            "Client-side RPC latency (streams: until exhausted)",
            ("service", "method"),
        )
    return _RPC_CLIENT_HANDLED, _RPC_CLIENT_LATENCY


_RPC_CLIENT_HANDLED = None
_RPC_CLIENT_LATENCY = None


def _incoming_traceparent(context) -> "str | None":
    try:
        for k, v in context.invocation_metadata() or ():
            if k == "traceparent":
                return v
    except Exception:
        return None
    return None


def _code_of_rpc_error(e: Exception) -> str:
    code = e.code() if hasattr(e, "code") else None
    if code is None:
        return "UNKNOWN"
    return code.name if hasattr(code, "name") else str(code)


class _InstrumentedStream:
    """Response-stream proxy: times the call to iterator exhaustion and
    records the outcome code once, while delegating everything else
    (``cancel``, ``code``, ``add_callback``…) to the underlying gRPC
    call object so existing stream handling keeps working. A stream the
    caller walks away from without exhausting (dfget returns on the
    first ``done=True`` result) finalizes at garbage collection with
    code ABANDONED — otherwise its span and client series never
    complete."""

    def __init__(self, call, finish: Callable[[str], None]):
        self._call = call
        self._finish = finish
        self._closed = False

    def _close(self, code: str) -> None:
        if not self._closed:
            self._closed = True
            self._finish(code)

    def __del__(self):
        try:
            self._close("ABANDONED")
        except Exception:
            pass  # interpreter teardown — never raise from __del__

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self._call)
        except StopIteration:
            self._close("OK")
            raise
        except grpc.RpcError as e:
            self._close(_code_of_rpc_error(e))
            raise
        except Exception:
            self._close("UNKNOWN")
            raise

    def cancel(self):
        self._close("CANCELLED")
        return self._call.cancel()

    def __getattr__(self, attr):
        return getattr(self._call, attr)


def _instrument_client(
    service: str, name: str, kind: str, callable_: Callable
) -> Callable:
    """Client-side call wrapper: injects the W3C ``traceparent`` header
    (from the caller's current span — a fresh root when none is active,
    so a CLI invocation still starts a trace) into invocation metadata,
    opens a client span, and records the rpc_client_* series.
    Response-streaming calls are timed to iterator exhaustion, like the
    server side."""
    streaming_out = kind in (UNARY_STREAM, STREAM_STREAM)

    def call(request_or_iterator, timeout=None, metadata=None, **kwargs):
        handled, latency = _rpc_client_metrics()
        parent = tracing.current_span()
        # record under the calling service's tracer when one is active
        # (the span rides its export file); a bare client gets its own
        tracer = (
            parent._tracer
            if parent is not None and parent._tracer is not None
            else tracing.get("client")
        )
        span = tracer.start_span(f"rpc.{name}", parent=parent, span_kind="client")
        md = list(metadata or ())
        # an explicitly provided traceparent wins — never stack a second
        if not any(k == tracing.TRACEPARENT_HEADER for k, _ in md):
            md.append((tracing.TRACEPARENT_HEADER, tracing.format_traceparent(span)))
        t0 = time.perf_counter()

        def finish(code: str) -> None:
            latency.labels(service, name).observe(time.perf_counter() - t0)
            handled.labels(service, name, code).inc()
            # an abandoned stream is normal API use (the caller got what
            # it needed), not a failed call
            span.end(
                status="ok"
                if code == "OK"
                else ("abandoned" if code == "ABANDONED" else "error")
            )

        try:
            result = callable_(
                request_or_iterator, timeout=timeout, metadata=md, **kwargs
            )
        except grpc.RpcError as e:
            finish(_code_of_rpc_error(e))
            raise
        except Exception:
            finish("UNKNOWN")
            raise
        if streaming_out:
            return _InstrumentedStream(result, finish)
        finish("OK")
        return result

    return call


def _instrument(service: str, name: str, kind: str, fn: Callable) -> Callable:
    """Wrap a handler behavior with counters + latency + a trace span.
    Response-streaming methods are timed to iterator exhaustion — the
    handler returns a generator, so wrapping the call alone would record
    only argument binding. The span parents under the caller's via the
    incoming ``traceparent`` metadata (absent/malformed → a new root),
    and is installed as the current span while the handler runs so
    application spans parent under it automatically."""
    handled, latency = _rpc_metrics()
    short = service.rsplit(".", 1)[-1]
    streaming_out = kind in (UNARY_STREAM, STREAM_STREAM)

    def wrapped(request_or_iterator, context):
        tracer = tracing.get(short)
        remote = tracing.parse_traceparent(_incoming_traceparent(context))
        span = tracer.start_span(f"rpc.{name}", parent=remote)
        t0 = time.perf_counter()

        def finish(code: str) -> None:
            latency.labels(service, name).observe(time.perf_counter() - t0)
            handled.labels(service, name, code).inc()
            span.end(status="ok" if code == "OK" else "error")

        # deadline-budget propagation (resilience layer): a request whose
        # caller already stopped waiting is shed before the handler runs —
        # finishing it would burn capacity the live requests need. The
        # remaining budget becomes this handler's ambient deadline, so
        # downstream client calls inherit (and further shrink) it.
        budget_ms = resilience.incoming_budget_ms(context.invocation_metadata())
        if resilience.shed_check(service, name, budget_ms):
            finish("DEADLINE_EXCEEDED")
            context.abort(
                grpc.StatusCode.DEADLINE_EXCEEDED, "deadline budget exhausted; shed"
            )
        deadline_at = (
            time.monotonic() + budget_ms / 1000.0 if budget_ms is not None else None
        )

        if not streaming_out:
            try:
                with tracing.use_span(span), resilience.absolute_deadline_scope(
                    deadline_at
                ):
                    resp = fn(request_or_iterator, context)
            except Exception:
                finish(_code_of(context))
                raise
            finish("OK")
            return resp

        def stream():
            # finally so abandonment is recorded too: a peer cancelling
            # mid-stream closes this generator (GeneratorExit, which
            # `except Exception` would miss) — exactly the broken-stream
            # case the series exists to surface. The span activates
            # around each resumption (not across yields): gRPC worker
            # threads are pooled, and a context left set at a yield
            # would leak into whatever runs on the thread next.
            code = "OK"
            gen = fn(request_or_iterator, context)
            try:
                while True:
                    # the deadline scope re-enters per resumption like the
                    # span: pooled gRPC threads must never inherit a stale
                    # deadline left across a yield
                    with tracing.use_span(span), resilience.absolute_deadline_scope(
                        deadline_at
                    ):
                        try:
                            item = next(gen)
                        except StopIteration:
                            break
                    yield item
            except GeneratorExit:
                code = "CANCELLED"
                gen.close()
                raise
            except Exception:
                code = _code_of(context)
                raise
            finally:
                finish(code)

        return stream()

    return wrapped


def _code_of(context) -> str:
    code = context.code()
    if code is None:
        return "UNKNOWN"
    return code.name if hasattr(code, "name") else str(code)


def make_handler(service: str, implementation: Any) -> grpc.GenericRpcHandler:
    """Bind an implementation object's methods as a generic service
    handler. Implementation methods receive (request_or_iterator, context)
    and return a response / iterator, like plugin-generated servicers."""
    methods = SERVICES[service]
    handlers: dict[str, grpc.RpcMethodHandler] = {}
    for name, m in methods.items():
        fn = _instrument(service, name, m.kind, getattr(implementation, name))
        factory = {
            UNARY: grpc.unary_unary_rpc_method_handler,
            UNARY_STREAM: grpc.unary_stream_rpc_method_handler,
            STREAM_UNARY: grpc.stream_unary_rpc_method_handler,
            STREAM_STREAM: grpc.stream_stream_rpc_method_handler,
        }[m.kind]
        handlers[name] = factory(
            fn,
            request_deserializer=m.request.FromString,
            response_serializer=m.response.SerializeToString,
        )
    return grpc.method_handlers_generic_handler(service, handlers)


# both ends of every channel: the announcer's Train stream ships the
# dataset in 128 MiB chunks (announcer.DEFAULT_UPLOAD_CHUNK), far past
# grpc's 4 MiB default — a limit raised on the dialing side alone still
# has the server refuse the first real upload with RESOURCE_EXHAUSTED
_MESSAGE_LIMITS = (
    ("grpc.max_send_message_length", 256 * 1024 * 1024),
    ("grpc.max_receive_message_length", 256 * 1024 * 1024),
)


def serve(
    implementations: dict[str, Any],
    address: str = "127.0.0.1:0",
    max_workers: int = 16,
    tls: "tuple[bytes, bytes] | None" = None,  # (key_pem, cert_pem)
    client_ca: bytes | None = None,  # require client certs signed by this CA
    extra_addresses: "list[str] | None" = None,
) -> tuple[grpc.Server, int]:
    """Start a server hosting {service_name: implementation}; returns
    (server, bound_port). With ``tls`` the port is TLS-terminated using
    the issued server cert (utils/issuer); ``client_ca`` additionally
    enforces mTLS (reference manager-issued certs, pkg/issuer +
    scheduler.go:179-218). ``extra_addresses`` bind the same services on
    additional listeners — e.g. ``unix:/run/dfdaemon.sock`` for the
    local-CLI path (reference pkg/rpc/mux.go serves tcp+unix+vsock from
    one grpc.Server); extras are plaintext, the filesystem is their
    access control."""
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers), options=_MESSAGE_LIMITS
    )
    for service, impl in implementations.items():
        server.add_generic_rpc_handlers((make_handler(service, impl),))
    if tls is not None:
        creds = grpc.ssl_server_credentials(
            [tls],
            root_certificates=client_ca,
            require_client_auth=client_ca is not None,
        )
        port = server.add_secure_port(address, creds)
    else:
        port = server.add_insecure_port(address)
    for extra in extra_addresses or []:
        server.add_insecure_port(extra)
    server.start()
    return server, port


def dial(
    address: str,
    retries: int = 3,
    backoff: float = 0.2,
    backoff_cap: float = 2.0,
    tls_ca: bytes | None = None,
    tls_client: "tuple[bytes, bytes] | None" = None,  # (key_pem, cert_pem)
    tls_server_name: str | None = None,
    ready_timeout: float = 5.0,
) -> grpc.Channel:
    """Channel with connection wait + retry-on-dial (reference pkg/rpc
    client dialing uses retry/backoff interceptors). Dial retries sleep
    the resilience layer's capped full-jitter backoff — the raw
    ``backoff * 2**attempt`` this used to run synchronizes every
    reconnecting client into lockstep thundering herds against a
    restarting server. ``tls_ca`` switches to TLS verifying the server
    against that root; ``tls_client`` adds the client pair for mTLS;
    ``tls_server_name`` overrides SNI/verification for certs issued to a
    different name."""
    options = list(_MESSAGE_LIMITS)
    if tls_server_name:
        options.append(("grpc.ssl_target_name_override", tls_server_name))
    last: Exception | None = None
    for attempt in range(retries):
        try:
            if tls_ca is not None:
                creds = grpc.ssl_channel_credentials(
                    root_certificates=tls_ca,
                    private_key=tls_client[0] if tls_client else None,
                    certificate_chain=tls_client[1] if tls_client else None,
                )
                channel = grpc.secure_channel(address, creds, options=options)
            else:
                channel = grpc.insecure_channel(address, options=options)
            grpc.channel_ready_future(channel).result(timeout=ready_timeout)
            return channel
        except Exception as e:  # pragma: no cover - network timing
            last = e
            channel.close()  # else the failed channel keeps reconnect threads alive
            if attempt + 1 < retries:  # no pointless sleep after the last try
                time.sleep(
                    resilience.full_jitter_backoff(
                        attempt, base_s=backoff, cap_s=backoff_cap
                    )
                )
    raise ConnectionError(f"failed to dial {address}: {last}")


# ---------------------------------------------------------------------------
# Consistent-hash scheduler selection
# ---------------------------------------------------------------------------


def _ring_hash(s: str) -> int:
    return int.from_bytes(hashlib.md5(s.encode()).digest()[:8], "big")


class ConsistentHashRing:
    """Pins a task ID to one scheduler across a multi-scheduler cluster
    (reference pkg/balancer/consistent_hashing.go:33-38) — every peer
    announcing task T talks to the same scheduler, so that scheduler sees
    the whole swarm for T.

    Mutations bump ``version`` (monotonic): the scheduler fleet's
    WRONG_SHARD retry loop compares versions to tell "my membership was
    stale and refreshing fixed it" from "the refusal came from a view I
    already hold" (scheduler/fleet.py, docs/fleet.md). A per-address
    vnode-hash index makes membership checks O(1) and ``add``
    idempotent without re-hashing; ``remove`` is one filtered pass over
    the flat ring — with a Python list that moves fewer elements than
    per-vnode bisect+pop would (each pop memmoves the tail, ~VNODES·R/2
    moves vs R), and never re-hashes anything."""

    VNODES = 100

    def __init__(self, addresses: list[str] | None = None):
        self._ring: list[tuple[int, str]] = []
        self._vnodes: dict[str, list[int]] = {}  # addr → its vnode hashes
        self.version = 0
        for addr in addresses or []:
            self.add(addr)

    def __contains__(self, address: str) -> bool:
        return address in self._vnodes

    def __len__(self) -> int:
        return len(self._vnodes)

    def addresses(self) -> list[str]:
        return list(self._vnodes)

    def add(self, address: str) -> None:
        if address in self._vnodes:
            return  # idempotent: a re-add must not double the vnodes
        hashes = [_ring_hash(f"{address}#{v}") for v in range(self.VNODES)]
        self._vnodes[address] = hashes
        for h in hashes:
            bisect.insort(self._ring, (h, address))
        self.version += 1

    def remove(self, address: str) -> None:
        if self._vnodes.pop(address, None) is None:
            return  # unknown member: no-op, no version bump
        self._ring = [e for e in self._ring if e[1] != address]
        self.version += 1

    def pick(self, key: str) -> str:
        if not self._ring:
            raise ValueError("no addresses in the ring")
        h = _ring_hash(key)
        i = bisect.bisect_left(self._ring, (h, ""))
        if i == len(self._ring):
            i = 0
        return self._ring[i][1]

    def successors(self, key: str, limit: int = 0) -> list[str]:
        """Distinct addresses in ring order starting at ``key``'s owner —
        element 0 is ``pick(key)``, the rest are the failover order a
        member death hands the key to (bounded hand-off: only keys whose
        owner died move, and they move to their successor)."""
        if not self._ring:
            return []
        h = _ring_hash(key)
        i = bisect.bisect_left(self._ring, (h, ""))
        out: list[str] = []
        seen: set[str] = set()
        n = len(self._ring)
        for step in range(n):
            addr = self._ring[(i + step) % n][1]
            if addr not in seen:
                seen.add(addr)
                out.append(addr)
                if limit and len(out) >= limit:
                    break
        return out


def serve_tls_args(
    cert_file: str = "", key_file: str = "", client_ca_file: str = ""
) -> dict:
    """PEM file paths → glue.serve TLS kwargs, validating that the
    config is all-or-nothing (a partially-set TLS config must fail
    loudly, never silently serve plaintext)."""
    if not (cert_file or key_file or client_ca_file):
        return {}
    if not (cert_file and key_file):
        raise ValueError(
            "TLS config incomplete: tls_cert_file and tls_key_file must both"
            " be set (tls_client_ca_file is optional, for mTLS)"
        )
    with open(key_file, "rb") as f:
        key = f.read()
    with open(cert_file, "rb") as f:
        cert = f.read()
    client_ca = None
    if client_ca_file:
        with open(client_ca_file, "rb") as f:
            client_ca = f.read()
    return {"tls": (key, cert), "client_ca": client_ca}


def dial_tls_args(
    ca_file: str = "",
    server_name: str = "",
    client_cert_file: str = "",
    client_key_file: str = "",
) -> dict:
    """CA (and optional client pair, for mTLS servers) file paths →
    glue.dial TLS kwargs."""
    if not ca_file:
        if client_cert_file or client_key_file:
            raise ValueError("client cert/key need the server CA file too")
        return {}
    with open(ca_file, "rb") as f:
        ca = f.read()
    out = {"tls_ca": ca}
    if server_name:
        out["tls_server_name"] = server_name
    if client_cert_file or client_key_file:
        if not (client_cert_file and client_key_file):
            raise ValueError(
                "mTLS client config incomplete: cert and key files must both be set"
            )
        with open(client_key_file, "rb") as f:
            key = f.read()
        with open(client_cert_file, "rb") as f:
            cert = f.read()
        out["tls_client"] = (key, cert)
    return out


class SchedulerSelector:
    """Multi-scheduler client set with consistent-hash task affinity
    (reference pkg/balancer/consistent_hashing.go wired as the gRPC
    loadBalancingPolicy; here an explicit selector the daemon drives).

    ``for_task(task_id)`` pins every RPC about a task to one scheduler so
    that scheduler sees the task's whole swarm; host-scoped calls
    (AnnounceHost/LeaveHost) fan out to every scheduler via ``all()``.
    A scheduler that cannot be dialed is skipped until the next use.
    """

    # Longer than the default announce interval (30s): a known-dead
    # scheduler is skipped for whole announce rounds instead of paying a
    # fresh serial connect timeout per round, which would delay
    # announcements to the healthy members.
    FAIL_COOLDOWN = 60.0
    # dead-address probes use a short ready wait; established channels
    # are cached, so this only bounds how long a DOWN scheduler stalls us
    DIAL_READY_TIMEOUT = 2.0

    def __init__(
        self,
        addresses: list[str],
        service: str = SCHEDULER_SERVICE,
        dial_kwargs: dict | None = None,
    ):
        self.addresses = [a.strip() for a in addresses if a.strip()]
        if not self.addresses:
            raise ValueError("no scheduler addresses")
        self.service = service
        self.dial_kwargs = dial_kwargs or {}
        self.ring = ConsistentHashRing(self.addresses)
        self._channels: dict[str, grpc.Channel] = {}
        self._clients: dict[str, ServiceClient] = {}
        self._fail_until: dict[str, float] = {}
        self._lock = threading.Lock()
        # optional live-membership feed (scheduler/fleet.py watcher):
        # () -> list[str] of currently-leased scheduler addresses, pulled
        # on demand by the WRONG_SHARD retry path
        self._membership_source: "Callable[[], list[str]] | None" = None

    def _client(self, addr: str) -> ServiceClient:
        with self._lock:
            client = self._clients.get(addr)
            if client is not None:
                return client
            until = self._fail_until.get(addr, 0.0)
            if until > time.monotonic():
                raise ConnectionError(f"{addr} in dial-failure cooldown")
        # dial OUTSIDE the lock — a dead scheduler's connect timeout must
        # not stall task routing to healthy, already-cached schedulers
        try:
            kw = {"ready_timeout": self.DIAL_READY_TIMEOUT, **self.dial_kwargs}
            channel = dial(addr, retries=1, **kw)
        except Exception:
            with self._lock:
                self._fail_until[addr] = time.monotonic() + self.FAIL_COOLDOWN
            raise
        with self._lock:
            existing = self._clients.get(addr)
            if existing is not None:
                channel.close()  # lost the race; reuse the cached one
                return existing
            if addr not in self.addresses:
                # update_addresses removed this scheduler while we were
                # dialing — caching now would leak a channel to a
                # decommissioned member that nothing ever closes
                channel.close()
                raise ConnectionError(f"{addr} removed from the scheduler set")
            self._channels[addr] = channel
            # target=addr: each scheduler gets its own circuit breaker and
            # retry budget — one dark member must not trip the others'
            client = self._clients[addr] = ServiceClient(
                channel, self.service, target=addr
            )
            self._fail_until.pop(addr, None)
            return client

    def update_addresses(self, addresses: list[str]) -> None:
        """Reconcile the scheduler set against a fresh dynconfig list:
        new addresses join the ring, removed ones leave it and their
        channels close (reference dynconfig-fed scheduler list — the
        daemon follows the manager's view of the cluster)."""
        fresh = [a.strip() for a in addresses if a.strip()]
        if not fresh:
            return  # an empty push must not strand the daemon schedulerless
        with self._lock:
            current = set(self.addresses)
            target = set(fresh)
            if current == target:
                return
            for addr in target - current:
                self.ring.add(addr)
            dead_channels = []
            for addr in current - target:
                self.ring.remove(addr)
                self._clients.pop(addr, None)
                ch = self._channels.pop(addr, None)
                if ch is not None:
                    dead_channels.append(ch)
                self._fail_until.pop(addr, None)
            self.addresses = fresh
        for ch in dead_channels:
            ch.close()

    # -- live-membership hooks (scheduler fleet, docs/fleet.md) ---------
    def set_membership_source(self, fn) -> None:
        """Wire a ``() -> list[str]`` returning the currently-leased
        scheduler addresses (the daemon's fleet watcher). The WRONG_SHARD
        retry loop pulls it to reconcile NOW instead of waiting out the
        next poll tick."""
        self._membership_source = fn

    def refresh_membership(self) -> bool:
        """Pull live membership once and reconcile the ring; True when
        the ring actually changed (the retry loop's staleness signal: an
        unchanged version means the refusal didn't come from membership
        lag on this side)."""
        fn = self._membership_source
        if fn is None:
            return False
        before = self.ring_version()
        try:
            members = fn()
        except Exception as e:
            dflog.get("rpc.selector").warning("membership refresh failed: %s", e)
            return False
        if members:
            self.update_addresses(members)
            with self._lock:
                # a live lease is fresh evidence the member is worth
                # dialing again: without this, one transient dial blip
                # puts a healthy owner in FAIL_COOLDOWN (60s) — far past
                # the wrong-shard retry window — and every task it owns
                # falls to back-to-source from this daemon
                for addr in members:
                    self._fail_until.pop(addr, None)
        return self.ring_version() != before

    def ring_version(self) -> int:
        with self._lock:
            return self.ring.version

    def ensure_address(self, address: str) -> None:
        """Adopt one address into the set (WRONG_SHARD owner hint: the
        refusing scheduler told us who owns the shard — believe it even
        before the membership poll catches up)."""
        address = address.strip()
        if not address:
            return
        with self._lock:
            if address in self.ring:
                return
            self.ring.add(address)
            self.addresses = self.addresses + [address]

    def client_for(self, address: str) -> ServiceClient:
        """Client for one specific member (WRONG_SHARD owner hint path);
        adopts the address into the set first so the ring agrees with
        where traffic actually goes. The hint is authoritative — the
        refusing scheduler just vouched for the owner's lease — so any
        dial-failure cooldown on it is cleared rather than honored."""
        self.ensure_address(address)
        with self._lock:
            self._fail_until.pop(address, None)
        return self._client(address)

    def resolve_for_task(
        self, task_id: str, avoid: "set[str] | None" = None
    ) -> tuple[str, ServiceClient]:
        """(address, client) for the task's ring owner — failing over
        along the ring successors when the owner is unreachable (a
        SIGKILL'd member must not error every task it owned until
        membership catches up; its keys hand off to their successor,
        reference consistent-hash balancer failover).

        Two health signals reorder the walk, because a cached channel to
        a dead member dials nothing and so never *raises* here: members
        the caller just failed against (``avoid`` — the conductor's
        stream-error feedback) and members whose circuit breaker is open
        inside its cool-down sort behind healthy candidates. They stay
        IN the walk as a last resort, so a fully-dark ring still probes
        rather than erroring blind."""
        avoid = avoid or set()
        with self._lock:
            candidates = self.ring.successors(task_id)
        if len(candidates) > 1:
            candidates.sort(
                key=lambda a: (a in avoid) + 2 * resilience.target_wide_open(a)
            )
        last: Exception | None = None
        for addr in candidates:
            try:
                return addr, self._client(addr)
            except Exception as e:
                last = e
        raise ConnectionError(f"no scheduler reachable for task: {last}")

    def for_task(self, task_id: str) -> ServiceClient:
        return self.resolve_for_task(task_id)[1]

    def addr_for_task(self, task_id: str) -> str:
        with self._lock:
            return self.ring.pick(task_id)

    def primary(self) -> ServiceClient:
        """First REACHABLE scheduler (probe loops etc.); raises only when
        every address is down."""
        with self._lock:
            addresses = list(self.addresses)
        last: Exception | None = None
        for addr in addresses:
            try:
                return self._client(addr)
            except Exception as e:
                last = e
        raise ConnectionError(f"no scheduler reachable: {last}")

    def all(self) -> list[ServiceClient]:
        # snapshot under the lock: update_addresses swaps self.addresses
        # from the membership reconcile thread, and the fan-out must see
        # one consistent set, not a torn read mid-swap
        with self._lock:
            addresses = list(self.addresses)
        out = []
        for addr in addresses:
            try:
                out.append(self._client(addr))
            except Exception:
                dflog.get("rpc.selector").warning(
                    "scheduler %s unreachable; skipping", addr
                )
        return out

    def close(self) -> None:
        with self._lock:
            for ch in self._channels.values():
                ch.close()
            self._channels.clear()
            self._clients.clear()
