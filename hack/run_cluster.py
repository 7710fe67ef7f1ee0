#!/usr/bin/env python3
"""Boot a full local cluster as real OS processes and drive a download
through it (reference deploy/docker-compose bring-up + test/e2e dfget):

    manager (gRPC + REST) → trainer → scheduler → 2 dfdaemons
    → dfget back-to-source through daemon A
    → dfget P2P through daemon B (pieces served by A)
    → verify bytes, a Download record on the scheduler, REST visibility

Exit code 0 = PASS. Used by hack/run_cluster.sh and the subprocess e2e
test (tests/test_cluster_subprocess.py).
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Proc:
    def __init__(self, name: str, args: list[str], env: dict):
        self.name = name
        self.proc = subprocess.Popen(
            [sys.executable, *args],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL if os.environ.get("DF_QUIET") else None,
            text=True,
            env=env,
            cwd=REPO,
        )
        self.addr: str | None = None
        self.metrics_addr: str | None = None
        self.rest_addr: str | None = None
        self.gateway_addr: str | None = None
        self.kv_addr: str | None = None
        # a dedicated reader thread avoids mixing select() on the raw fd
        # with buffered readline() (lines stranded in the TextIOWrapper
        # buffer would make select starve)
        self._lines: "queue.Queue[str | None]" = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def wait_ready(self, timeout: float = 120.0) -> str:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None and self._lines.empty():
                raise RuntimeError(f"{self.name} exited rc={self.proc.returncode}")
            try:
                line = self._lines.get(timeout=1.0)
            except queue.Empty:
                continue
            if line is None:
                continue
            if line.startswith("METRICS "):
                self.metrics_addr = line.split()[2]
            if line.startswith("REST "):
                self.rest_addr = line.split()[2]
            if line.startswith("GATEWAY "):
                self.gateway_addr = line.split()[2]
            if line.startswith("KV "):
                self.kv_addr = line.split()[2]
            if line.startswith("READY "):
                self.addr = line.split()[2]
                return self.addr
        raise TimeoutError(f"{self.name} not READY within {timeout}s")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def main() -> int:
    work = tempfile.mkdtemp(prefix="dfcluster-")
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        PYTHONUNBUFFERED="1",
        # a CPU harness: trainer and scheduler each own device planes,
        # and a chip belongs to one process at a time
        JAX_PLATFORMS="cpu",
        # service-plane spans in OTLP/JSON — the round-5 wire-parity leg:
        # every line a complete ExportTraceServiceRequest the otel
        # collector's otlpjsonfile receiver (→ Jaeger) ingests
        DF_TRACE_DIR=os.path.join(work, "traces"),
        DF_TRACE_FORMAT="otlp",
    )
    procs: list[Proc] = []
    try:
        manager = Proc(
            "manager",
            [
                "-m",
                "dragonfly2_tpu.manager",
                "--set",
                f"data_dir={work}/manager",
                "--set",
                "rest_port=0",
            ],
            env,
        )
        procs.append(manager)
        manager_addr = manager.wait_ready()

        trainer = Proc(
            "trainer",
            [
                "-m",
                "dragonfly2_tpu.trainer",
                "--set",
                f"data_dir={work}/trainer",
                "--set",
                f"manager_address={manager_addr}",
                # GRU trains by default (TrainingConfig.gru); the smoke
                # swarm yields only a handful of sequences, so lower the
                # floor the leg needs to fit
                "--set",
                "gru_min_sequences=1",
            ],
            env,
        )
        procs.append(trainer)
        trainer_addr = trainer.wait_ready()

        scheduler = Proc(
            "scheduler",
            [
                "-m",
                "dragonfly2_tpu.scheduler",
                "--set",
                f"data_dir={work}/scheduler",
                "--set",
                f"manager_address={manager_addr}",
                "--set",
                f"trainer_address={trainer_addr}",
                "--set",
                "algorithm=ml",
                "--set",
                "storage_buffer_size=1",
                "--set",
                "hostname=sched-e2e",
                "--set",
                "metrics_port=0",
                # export the probe graph as NetworkTopology records fast
                # enough for the GNN train leg (reference default: 2h)
                "--set",
                "topology_snapshot_interval=2.0",
            ],
            env,
        )
        procs.append(scheduler)
        scheduler_addr = scheduler.wait_ready()

        sock_a = f"{work}/dfdaemon-a.sock"
        daemons = []
        for name in ("a", "b"):
            args = [
                "-m",
                "dragonfly2_tpu.client.daemon",
                "--set",
                f"data_dir={work}/daemon-{name}",
                "--set",
                f"hostname=host-{name}",
                "--set",
                "piece_length=65536",
                "--set",
                "schedule_timeout=10.0",
                # fast prober so SyncProbes populates the scheduler's
                # probe graph within the script's lifetime (the GNN
                # train leg below consumes its snapshot)
                "--set",
                "probe_interval=0.5",
            ]
            if name == "a":
                # daemon A: static scheduler list + unix socket (the
                # local-CLI path dfget drives below)
                args += [
                    "--set", f"scheduler_address={scheduler_addr}",
                    "--set", f"unix_socket={sock_a}",
                ]
            else:
                # daemon B: no static list — scheduler set discovered
                # from the manager (dynconfig), and it registers itself
                # as a seed peer; also fronts the object-storage gateway
                args += [
                    "--set", 'scheduler_address=""',
                    "--set", f"manager_address={manager_addr}",
                    "--set", "host_type=super",
                    "--set", "object_storage_port=0",
                    "--set", f"object_storage_dir={work}/objects",
                ]
            d = Proc(f"daemon-{name}", args, env)
            procs.append(d)
            daemons.append(d)
        daemon_addrs = [d.wait_ready() for d in daemons]
        daemon_addrs[0] = f"unix:{sock_a}"

        # origin file (file:// keeps the script hermetic; http origins are
        # covered by the in-process e2e tests)
        payload = os.urandom(300 * 1024)
        origin = os.path.join(work, "origin.bin")
        with open(origin, "wb") as f:
            f.write(payload)
        url = f"file://{origin}"

        # dfget through daemon A: back-to-source
        out_a = os.path.join(work, "out-a.bin")
        rc = subprocess.run(
            [
                sys.executable,
                "-m",
                "dragonfly2_tpu.client.dfget",
                url,
                "-O",
                out_a,
                "--daemon",
                daemon_addrs[0],
            ],
            env=env,
            cwd=REPO,
            capture_output=True,
            text=True,
        )
        assert rc.returncode == 0, f"dfget A failed: {rc.stderr[-2000:]}"
        assert open(out_a, "rb").read() == payload, "daemon A bytes mismatch"
        print("PASS dfget back-to-source via daemon A (unix socket)")

        # dfget through daemon B: must pull pieces from A over P2P
        out_b = os.path.join(work, "out-b.bin")
        rc = subprocess.run(
            [
                sys.executable,
                "-m",
                "dragonfly2_tpu.client.dfget",
                url,
                "-O",
                out_b,
                "--daemon",
                daemon_addrs[1],
            ],
            env=env,
            cwd=REPO,
            capture_output=True,
            text=True,
        )
        assert rc.returncode == 0, f"dfget B failed: {rc.stderr[-2000:]}"
        assert open(out_b, "rb").read() == payload, "daemon B bytes mismatch"
        print("PASS dfget P2P via daemon B")

        # ranged dfget: the slice is its own task, correct bytes only
        out_r = os.path.join(work, "out-range.bin")
        rc = subprocess.run(
            [
                sys.executable, "-m", "dragonfly2_tpu.client.dfget",
                url, "-O", out_r,
                "--daemon", daemon_addrs[1],
                "--range", "1000-65999",
            ],
            env=env, cwd=REPO, capture_output=True, text=True,
        )
        assert rc.returncode == 0, f"ranged dfget failed: {rc.stderr[-2000:]}"
        assert open(out_r, "rb").read() == payload[1000:66000], "ranged bytes mismatch"
        print("PASS ranged dfget (--range) via daemon B")

        # zero-byte origin: completes as an empty file through both
        # daemons (reference feature gate dfget-empty-file); the
        # scheduler must record the true length 0, not stay "unknown"
        empty_origin = os.path.join(work, "empty.bin")
        open(empty_origin, "wb").close()
        for i, addr in enumerate(daemon_addrs):
            out_e = os.path.join(work, f"out-empty-{i}.bin")
            rc = subprocess.run(
                [
                    sys.executable, "-m", "dragonfly2_tpu.client.dfget",
                    f"file://{empty_origin}", "-O", out_e, "--daemon", addr,
                ],
                env=env, cwd=REPO, capture_output=True, text=True,
            )
            assert rc.returncode == 0, f"empty dfget {i} failed: {rc.stderr[-2000:]}"
            assert os.path.getsize(out_e) == 0, "empty download must be empty"
        print("PASS empty-file dfget via both daemons")

        # dfcache: import a local file into the cache through the real
        # daemon binary, stat it, export it back (reference dfcache e2e)
        cache_src = os.path.join(work, "cache-src.bin")
        with open(cache_src, "wb") as f:
            f.write(os.urandom(70 * 1024))
        cache_url = "d7y:///cache-e2e"
        for cmd_args in (
            ["import", cache_url, "--path", cache_src],
            ["stat", cache_url],
            # --local-only on export: the step must assert a LOCAL cache
            # hit — without it a miss falls back to "downloading" the
            # unresolvable d7y:// url instead of failing crisply
            [
                "export", cache_url, "--local-only",
                "--output", os.path.join(work, "cache-out.bin"),
            ],
        ):
            rc = subprocess.run(
                [
                    sys.executable, "-m", "dragonfly2_tpu.client.dfcache",
                    *cmd_args, "--daemon", daemon_addrs[0],
                ],
                env=env, cwd=REPO, capture_output=True, text=True, timeout=60,
            )
            assert rc.returncode == 0, (
                f"dfcache {cmd_args[0]} failed: {rc.stderr[-2000:]}"
            )
        assert (
            open(os.path.join(work, "cache-out.bin"), "rb").read()
            == open(cache_src, "rb").read()
        ), "dfcache export bytes mismatch"
        print("PASS dfcache import/stat/export via daemon A")

        # dfstore: object put/stat/get through daemon B's real gateway
        # process (S3-verb surface; upload seeds the swarm)
        gateway = daemons[1].gateway_addr
        assert gateway, "daemon B did not report a GATEWAY address"
        store_src = os.path.join(work, "store-src.bin")
        with open(store_src, "wb") as f:
            f.write(os.urandom(90 * 1024))
        store_out = os.path.join(work, "store-out.bin")
        for cmd_args in (
            ["mb", "df://e2e"],
            ["cp", store_src, "df://e2e/dir/obj.bin"],
            ["stat", "df://e2e/dir/obj.bin"],
            ["cp", "df://e2e/dir/obj.bin", store_out],
        ):
            rc = subprocess.run(
                [
                    sys.executable, "-m", "dragonfly2_tpu.client.dfstore",
                    "--endpoint", gateway, *cmd_args,
                ],
                env=env, cwd=REPO, capture_output=True, text=True, timeout=60,
            )
            assert rc.returncode == 0, (
                f"dfstore {cmd_args[0]} failed: {rc.stderr[-2000:]}"
            )
        assert (
            open(store_out, "rb").read() == open(store_src, "rb").read()
        ), "dfstore round-trip bytes mismatch"
        print("PASS dfstore mb/cp/stat round-trip via daemon B gateway")

        # stress tool: concurrent load through the daemon RPC, one JSON
        # line of percentiles (reference test/tools/stress)
        rc = subprocess.run(
            [
                sys.executable, "-m", "dragonfly2_tpu.tools.stress",
                "--url", url, "--daemon", daemon_addrs[1], "-c", "3", "-n", "9",
            ],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert rc.returncode == 0, f"stress failed: {rc.stderr[-2000:]}"
        stress_stats = json.loads(rc.stdout.strip().splitlines()[-1])
        assert stress_stats["failures"] == 0 and stress_stats["requests"] >= 9, (
            f"stress run unhealthy: {stress_stats}"
        )
        print(
            "PASS stress load generator"
            f" (p50 {stress_stats['latency_s']['p50']}s,"
            f" {stress_stats['throughput_mb_s']} MB/s)"
        )

        # training records landed on the scheduler
        records_dir = os.path.join(work, "scheduler", "records")
        deadline = time.time() + 10
        have_records = False
        while time.time() < deadline and not have_records:
            for root, _, files in os.walk(records_dir):
                if any(f.startswith("download") and f.endswith(".csv") for f in files):
                    have_records = True
            time.sleep(0.2)
        assert have_records, f"no download records under {records_dir}"
        print("PASS download records written")

        # scheduler /metrics scrape shows the download actually moved
        # the instrumented series
        assert scheduler.metrics_addr, "scheduler did not report a metrics address"
        with urllib.request.urlopen(
            f"http://{scheduler.metrics_addr}/metrics", timeout=5
        ) as resp:
            series = resp.read().decode()
        assert "dragonfly_scheduler_announce_peer_total" in series
        assert 'dragonfly_scheduler_register_peer_total' in series
        print("PASS scheduler metrics scrape")

        # manager sees the registered scheduler (gRPC registry; the REST
        # surface gets its own stanza below)
        sys.path.insert(0, REPO)
        from dragonfly2_tpu.rpc import glue, gen  # noqa: F401
        import manager_pb2
        from dragonfly2_tpu.manager.service import SERVICE_NAME

        ch = glue.dial(manager_addr)
        client = glue.ServiceClient(ch, SERVICE_NAME)
        resp = client.ListSchedulers(manager_pb2.ListSchedulersRequest())
        names = [s.hostname for s in resp.schedulers]
        assert "sched-e2e" in names, f"scheduler not registered: {names}"
        ch.close()
        print("PASS scheduler registered with manager")

        # v1 wire generation bound in the production scheduler binary:
        # StatTask over the v1 service sees the downloaded task
        from dragonfly2_tpu.rpc.glue import SCHEDULER_V1_SERVICE
        from dragonfly2_tpu.utils.idgen import task_id_v1
        import scheduler_v1_pb2 as v1

        ch = glue.dial(scheduler_addr)
        v1c = glue.ServiceClient(ch, SCHEDULER_V1_SERVICE)
        stat = v1c.StatTask(v1.StatTaskRequest(task_id=task_id_v1(url, None)))
        assert stat.state == "Succeeded" and stat.has_available_peer, stat
        ch.close()
        print("PASS v1 wire generation serves the same swarm")

        # REST surface: console page, user bootstrap → signin → PAT →
        # authenticated API call
        rest = manager.rest_addr
        assert rest, "manager did not report a REST address"

        def call(method, path, body=None, token=None):
            req = urllib.request.Request(
                f"http://{rest}{path}",
                method=method,
                data=json.dumps(body).encode() if body is not None else None,
                headers={
                    "Content-Type": "application/json",
                    **({"Authorization": f"Bearer {token}"} if token else {}),
                },
            )
            with urllib.request.urlopen(req, timeout=5) as r:
                return json.loads(r.read())

        page = urllib.request.urlopen(f"http://{rest}/", timeout=5).read().decode()
        assert "Dragonfly2-TPU" in page and "/api/v1/models" in page
        user = call("POST", "/api/v1/users", {"name": "op", "password": "pw", "role": "admin"})
        session = call("POST", "/api/v1/users/signin", {"name": "op", "password": "pw"})
        pat = call(
            "POST",
            f"/api/v1/users/{user['id']}/personal-access-tokens",
            {"name": "e2e"},
            token=session["token"],
        )
        rows = call("GET", "/api/v1/schedulers", token=pat["token"])
        assert any(r["hostname"] == "sched-e2e" for r in rows), rows
        print("PASS console + users/PAT auth over REST")

        # daemon B discovered its scheduler from the manager AND
        # registered itself as a seed peer (visible over REST)
        rows = call("GET", "/api/v1/seed-peers", token=pat["token"])
        assert any(r["hostname"] == "host-b" for r in rows), rows
        print("PASS manager-fed discovery + seed-peer registration")

        # train→serve round-trip at subprocess level: the scheduler's
        # Download records stream over the trainer's Train RPC, EOF
        # fires the fit, the model lands in the manager registry, and
        # activation flips it live (SURVEY §3.3)
        import glob as _glob

        import trainer_pb2

        csvs = [
            p
            for p in _glob.glob(
                os.path.join(records_dir, "**", "download*.csv"), recursive=True
            )
            if os.path.isfile(p)
        ]
        assert csvs, "no download CSVs to upload"

        # the probe loop (probe_interval=0.5 above) + snapshot timer
        # (topology_snapshot_interval=2.0) must have exported probe-graph
        # records by now — the GNN leg trains on them
        def _topo_csvs():
            return [
                p
                for p in _glob.glob(
                    os.path.join(records_dir, "**", "networktopology*.csv"),
                    recursive=True,
                )
                if os.path.isfile(p) and os.path.getsize(p) > 0
            ]

        deadline = time.time() + 60
        topo = _topo_csvs()
        while time.time() < deadline and not topo:
            time.sleep(0.5)
            topo = _topo_csvs()
        assert topo, f"no networktopology CSVs under {records_dir}"
        print("PASS probe loop exported NetworkTopology records")

        tchan = glue.dial(trainer_addr)
        tclient = glue.ServiceClient(tchan, glue.TRAINER_SERVICE)

        def _train_reqs():
            for p in csvs:
                with open(p, "rb") as f:
                    data = f.read()
                yield trainer_pb2.TrainRequest(
                    ip="10.99.0.1",
                    hostname="sched-e2e",
                    train_mlp=trainer_pb2.TrainMlpRequest(dataset=data),
                )
            for p in topo:
                with open(p, "rb") as f:
                    data = f.read()
                yield trainer_pb2.TrainRequest(
                    ip="10.99.0.1",
                    hostname="sched-e2e",
                    train_gnn=trainer_pb2.TrainGnnRequest(dataset=data),
                )

        tclient.Train(_train_reqs(), timeout=600)
        tchan.close()
        models = {}
        deadline = time.time() + 240
        while time.time() < deadline and len(models) < 3:
            rows = call("GET", "/api/v1/models", token=pat["token"])
            models = {r["type"]: r for r in rows}
            time.sleep(1)
        # NOTE: no early exit once some models land — on a 1-core CI box
        # the three fits' first XLA compiles run concurrently and the
        # slowest can trail the others by minutes; "two landed, third
        # missing" does NOT imply the third failed
        missing_hint = "(check the trainer proc's log for the fit error)"
        assert "mlp" in models, f"no MLP model uploaded: {sorted(models)} {missing_hint}"
        assert "gnn" in models, f"no GNN model uploaded: {sorted(models)} {missing_hint}"
        assert "gru" in models, f"no GRU model uploaded: {sorted(models)} {missing_hint}"
        model = models["mlp"]
        act = call(
            "PUT",
            f"/api/v1/models/{model['model_id']}/versions/{model['version']}/state",
            {"state": "active"},
            token=pat["token"],
        )
        assert act["state"] == "active"
        print(
            "PASS train-serve roundtrip (records -> Train RPC -> MLP+GNN+GRU"
            f" fits -> CreateModel → activation; models={sorted(models)})"
        )

        # dynamic certificate issuance: CSR → booted manager's CA →
        # chain that verifies against the persisted root
        from dragonfly2_tpu.utils.issuer import obtain_certificate

        key_pem, leaf_pem, ca_pem = obtain_certificate(
            manager_addr, "e2e-service", hosts=["localhost", "127.0.0.1"]
        )
        assert b"BEGIN CERTIFICATE" in leaf_pem and b"BEGIN CERTIFICATE" in ca_pem
        on_disk_ca = open(os.path.join(work, "manager", "ca", "ca.crt"), "rb").read()
        assert ca_pem == on_disk_ca, "returned chain root must be the persisted CA"
        print("PASS dynamic certificate issuance (CSR → manager CA)")

        # OTLP trace export: the booted binaries wrote span files whose
        # every line parses as an ExportTraceServiceRequest
        trace_files = _glob.glob(os.path.join(work, "traces", "*.otlp.jsonl"))
        assert trace_files, "no OTLP trace files written"
        span_count = 0
        services = set()
        for tf in trace_files:
            for line in open(tf):
                try:
                    req = json.loads(line)
                except json.JSONDecodeError:
                    # the services are still running — a final line may
                    # be mid-write; completed lines are the contract
                    continue
                rs = req["resourceSpans"][0]
                svc = {
                    a["key"]: a["value"]["stringValue"]
                    for a in rs["resource"]["attributes"]
                }
                services.add(svc["service.name"])
                for sp in rs["scopeSpans"][0]["spans"]:
                    assert len(sp["traceId"]) == 32 and len(sp["spanId"]) == 16
                    span_count += 1
        assert span_count > 0
        print(
            f"PASS OTLP trace export ({span_count} spans from {sorted(services)})"
        )

        print("CLUSTER E2E: ALL PASS")
        return 0
    finally:
        for p in reversed(procs):
            p.stop()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
