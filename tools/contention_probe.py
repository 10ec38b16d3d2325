"""Contention probe: a small copy of the `sysbench_ro_uniform` cell for comparing checkouts under 16 clients.

    cd <checkout> && python3 <path to>/contention_probe.py <label> [seconds]

Runs in the checkout given as the working directory, so that variants of the tree can be laid side by side and
run in turn on one machine (PERF.md section 6, PR 26, off-cost): starts `MySQLServer`, loads one table of 16,384
sysbench rows, switches JAX's persistent compile cache off, then lets 16 `MiniClient` threads repeat the
oltp_read_only transaction with fresh literals for `seconds` (default 40).  Prints one JSON line: operations per
second, median latency, process CPU seconds per operation, the socket sends and receives per operation of both
ends of the wire together (every `PacketIO` of the process; null on a checkout whose `PacketIO` does not count
them) beside the reply packets, statement medians, the device.  Not part of the benchmark and read by nothing:
its numbers compare two trees in one call, no more."""

import json
import os
import statistics
import sys
import threading
import time

ROWS, CLIENTS = 16384, 16
STATEMENTS = {
    "point_select": "select c from sbtest1 where id = {a}",
    "simple_range": "select c from sbtest1 where id between {a} and {b}",
    "sum_range": "select sum(k) from sbtest1 where id between {a} and {b}",
    "order_range": "select c from sbtest1 where id between {a} and {b} order by c",
    "distinct_range": "select distinct c from sbtest1 where id between {a} and {b} order by c",
}


def operation(conn, n: int, latencies: dict) -> None:
    """One oltp_read_only transaction: ten point gets and the four ranges, literals from `n`."""
    conn.query("begin")
    for name, sql in STATEMENTS.items():
        for rep in range(10 if name == "point_select" else 1):
            a = 1 + (n * 613 + len(name) * 37 + rep * 101) % (ROWS - 200)
            t = time.perf_counter()
            conn.query(sql.format(a=a, b=a + 99))
            latencies[name].append((time.perf_counter() - t) * 1e3)
    conn.query("commit")


def main(label: str, seconds: float) -> None:
    sys.path.insert(0, os.getcwd())
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import tidb_tpu  # noqa: F401  (enables x64)
    from tidb_tpu.server import MiniClient, MySQLServer, protocol
    from tidb_tpu.util import metrics

    wires: list = []  # every PacketIO of the process, the server's and the clients'
    made = protocol.PacketIO.__init__

    def counted(io, sock) -> None:
        made(io, sock)
        wires.append(io)

    protocol.PacketIO.__init__ = counted

    def socket_calls() -> dict:
        return {k: sum(getattr(io, k) for io in wires) if hasattr(wires[0], k) else None for k in ("sends", "recvs")}

    srv = MySQLServer(port=0)
    srv.start_background()
    admin = MiniClient(srv.host, srv.port, timeout=600)
    admin.query("create table sbtest1 (id int primary key auto_increment, k int not null default 0, "
                "c char(120) not null default '', pad char(60) not null default '', key k_1(k))")
    for lo in range(1, ROWS + 1, 2048):
        admin.query("insert into sbtest1 values " + ",".join(
            f"({i},{(i * 7919) % ROWS},'{(str(i * 104729 % 10**11).zfill(11) + '-') * 10}',"
            f"'{(str(i * 1299709 % 10**11).zfill(11) + '-') * 5}')" for i in range(lo, lo + 2048)))
    conns = [MiniClient(srv.host, srv.port, timeout=600) for _ in range(CLIENTS)]
    operation(conns[0], 0, {k: [] for k in STATEMENTS})  # the shapes' first execution
    jax.config.update("jax_enable_compilation_cache", False)  # every fresh literal compiles, as in the cell's window
    compilation_cache.reset_cache()

    latencies = {k: [] for k in STATEMENTS}
    done: list = []
    close_at = time.perf_counter() + seconds

    def loop(i: int) -> None:
        n = 1000 * (i + 1)
        while time.perf_counter() < close_at:
            t = time.perf_counter()
            operation(conns[i], n, latencies)
            done.append(time.perf_counter() - t)
            n += 1

    calls0, packets0 = socket_calls(), metrics.SERVER_PACKETS_OUT.value
    cpu0, t0 = time.process_time(), time.perf_counter()
    threads = [threading.Thread(target=loop, args=(i,)) for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall, cpu = time.perf_counter() - t0, time.process_time() - cpu0
    calls = socket_calls()
    print(json.dumps({
        "label": label, "clients": CLIENTS, "ops": len(done), "ops_per_s": round(len(done) / wall, 4),
        "op_p50_ms": round(statistics.median(done) * 1e3, 1), "process_cpu_s_per_op": round(cpu / len(done), 3),
        "reply_packets_per_op": round((metrics.SERVER_PACKETS_OUT.value - packets0) / len(done), 1),
        **{f"socket_{k}_per_op": None if n is None else round((n - calls0[k]) / len(done), 1)
           for k, n in calls.items()},
        "p50_ms": {k: round(statistics.median(v), 2) for k, v in latencies.items()},
        "device": jax.devices()[0].platform}), flush=True)
    for conn in conns + [admin]:
        conn.close()
    srv.close()


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]) if len(sys.argv) > 2 else 40.0)
