"""MySQL protocol server over the embedded engine (ref: pkg/server/server.go
accept loop, conn.go clientConn.Run/dispatch/writeResultSet;
cmd/tidb-server/main.go wiring).

One OS thread per connection (the reference runs one goroutine per conn);
every connection gets its own Session over the shared store + catalog, so
transactions, sysvars and temporary state are per-connection exactly like
the reference's session management."""

from __future__ import annotations

import socket
import socketserver
import threading

from ..sql import Session, SQLError
from ..sql.catalog import Catalog, CatalogError
from ..sql.planner import PlanError
from ..store import TPUStore
from ..types import Datum, DatumKind, Flag
from ..util import metrics, tracing
from . import protocol as P


def datum_text(d: Datum) -> str | None:
    """Datum -> text-protocol cell (ref: dumpTextRow value formatting)."""
    if d.is_null():
        return None
    if d.kind == DatumKind.Bytes:
        v = d.val
        return v.decode("utf-8", "surrogateescape") if isinstance(v, bytes) else str(v)
    if d.kind in (DatumKind.Float32, DatumKind.Float64):
        v = float(d.val)
        if v == int(v) and abs(v) < 1e15:
            return str(int(v))
        return repr(v)
    if d.kind == DatumKind.MysqlJSON:
        from ..types import json_binary as jb

        return jb.to_text(jb.decode(d.val))
    return str(d.val)


def column_flags(ft) -> int:
    flags = 0
    if ft.flag & Flag.NotNull:
        flags |= 1  # NOT_NULL_FLAG
    if ft.is_unsigned():
        flags |= 32  # UNSIGNED_FLAG
    return flags


class Connection:
    def __init__(self, sock, server, conn_id: int):
        self.io = P.PacketIO(sock)
        self.server = server
        self.conn_id = conn_id
        self.session = Session(server.store, server.catalog, config=server.config)

    # ------------------------------------------------------------------
    def handshake(self) -> bool:
        """Greeting, the client's response, OK or ERR 1045; each reply is
        flushed before the peer is waited for or the socket is closed."""
        salt = P.new_salt()
        self.io.write(P.handshake_v10(self.conn_id, salt))
        self.io.flush()
        resp = P.parse_handshake_response(self.io.read())
        user = resp["user"]
        if self.server.users:
            stored = self.server.users.get(user)  # explicit override map
        else:
            # CREATE USER records (ref: privilege cache feeding auth)
            stored = self.server.catalog.privileges.password_of(user)
        granted = stored is not None and P.check_auth(stored, salt, resp["auth"])
        if granted:
            if not self.server.users:
                # privilege-store users run as themselves; the explicit override
                # map is a test shortcut whose users bypass privilege checks
                self.session.user = user.lower()
            self.io.write(P.ok_packet(status=self._status()))
        else:
            self.io.write(P.err_packet(1045, f"Access denied for user '{user}'", "28000"))
        self.io.flush()
        return granted

    def _status(self) -> int:
        st = P.SERVER_STATUS_AUTOCOMMIT
        if self.session.txn is not None:
            st |= P.SERVER_STATUS_IN_TRANS
        return st

    # ------------------------------------------------------------------
    def run(self):
        """The command loop.  A command is clocked from its packet's read
        until its last reply byte is handed to the socket; the wait for the
        client's next packet is not.  Replies are buffered (protocol.py):
        a query's leave with its last statement's result, whatever another
        command or an error path wrote leaves here, before the command is
        booked and the next one read."""
        io = self.io
        while True:
            io.reset()
            try:
                pkt = io.read()
            except (ConnectionError, OSError):
                return
            if not pkt:
                continue
            if pkt[0] == P.COM_QUIT:
                return
            packets, sends = io.packets_out, io.sends
            # the bottom state of the thread's clock (util/tracing.py): its
            # two reads are the command's, so the states' sums add up to it;
            # the thread only waited for the packet since its last command
            command = tracing.host_state("server.command", cpu="ticks")
            try:
                with command:
                    self.dispatch(pkt[0], pkt[1:])
                    io.flush()
            finally:
                metrics.SERVER_PACKETS_OUT.inc(io.packets_out - packets)
                metrics.SERVER_SOCKET_SENDS.inc(io.sends - sends)
                metrics.SERVER_HANDLE_NS.inc(command.wall_ns)
                metrics.SERVER_CPU_NS.inc(command.cpu_ns)
                metrics.SERVER_COMMANDS.inc()  # last: a reader that sees the command sees its time and packets

    def dispatch(self, cmd: int, payload: bytes):
        if cmd in (P.COM_PING, P.COM_INIT_DB):
            self.io.write(P.ok_packet(status=self._status()))
        elif cmd == P.COM_FIELD_LIST:
            self.io.write(P.eof_packet(self._status()))
        elif cmd == P.COM_QUERY:
            self.handle_query(payload.decode("utf-8", "replace"))
        elif cmd in (P.COM_STMT_PREPARE, P.COM_STMT_EXECUTE, P.COM_STMT_CLOSE):
            self.io.write(P.err_packet(1295, "binary protocol not supported; use text PREPARE/EXECUTE"))
        else:
            self.io.write(P.err_packet(1047, f"unknown command {cmd}"))

    def handle_query(self, sql: str):
        """(ref: conn.go handleQuery -> handleStmt -> writeResultSet)."""
        from ..parser.parser import ParseError

        stmts = split_statements(sql)
        for i, stmt_sql in enumerate(stmts):
            try:
                res = self.session.execute(stmt_sql)
            except (SQLError, PlanError, CatalogError, ParseError) as exc:
                # typed statement errors carry their MySQL errno (9005
                # region-unavailable, 3024/1317 killed, 9007 write conflict,
                # 1205 lock wait timeout, 1213 deadlock); the rest are 1105
                self.io.write(P.err_packet(getattr(exc, "code", 1105), str(exc)))
                return
            except Exception as exc:  # noqa: BLE001 — wire must answer
                self.io.write(P.err_packet(1105, f"internal error: {exc}"))
                return
            last = i + 1 == len(stmts)
            io = self.io
            with tracing.span("server.write") as sp:
                packets, nbytes, sends = io.packets_out, io.bytes_out, io.sends
                self.write_result(res, more=not last)
                if last:
                    io.flush()  # the command's whole reply, in one send unless it outgrew the buffer
                if sp is not None:
                    sp.attrs.update(packets=io.packets_out - packets, bytes=io.bytes_out - nbytes,
                                    sends=io.sends - sends)

    SERVER_MORE_RESULTS = 0x0008

    def write_result(self, res, more: bool = False):
        status = self._status() | (self.SERVER_MORE_RESULTS if more else 0)
        if not res.columns:
            self.io.write(P.ok_packet(affected=res.affected, status=status))
            return
        fts = getattr(res, "fts", None)
        self.io.write(P.lenenc_int(len(res.columns)))
        for i, name in enumerate(res.columns):
            ft = fts[i] if fts else None
            if ft is not None:
                self.io.write(P.column_def(str(name), int(ft.tp), ft.flen, max(ft.decimal, 0), column_flags(ft)))
            else:
                self.io.write(P.column_def(str(name), 0xFD))  # VAR_STRING
        self.io.write(P.eof_packet(status))
        for row in res.rows:
            self.io.write(P.text_row([datum_text(d) for d in row]))
        self.io.write(P.eof_packet(status))


def split_statements(sql: str) -> list[str]:
    """Split a COM_QUERY payload on top-level semicolons (multi-statement
    support; quote-aware, no comment handling beyond trailing whitespace)."""
    out, buf, quote = [], [], None
    i = 0
    while i < len(sql):
        ch = sql[i]
        if quote:
            buf.append(ch)
            if ch == quote and not (i + 1 < len(sql) and sql[i + 1] == quote):
                quote = None
            elif ch == quote:
                buf.append(sql[i + 1])
                i += 1
            elif ch == "\\" and i + 1 < len(sql):
                buf.append(sql[i + 1])
                i += 1
        elif ch in ("'", '"', "`"):
            quote = ch
            buf.append(ch)
        elif ch == ";":
            s = "".join(buf).strip()
            if s:
                out.append(s)
            buf = []
        else:
            buf.append(ch)
        i += 1
    s = "".join(buf).strip()
    if s:
        out.append(s)
    return out


class MySQLServer:
    """(ref: server.NewServer + Run). Listens on a TCP port; serves each
    connection on a thread. `users` maps user -> password bytes; empty map
    = accept anyone (the mock default)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 store: TPUStore | None = None, catalog: Catalog | None = None,
                 users: dict | None = None, config=None):
        self.store = store or TPUStore()
        self.catalog = catalog or Catalog()
        self.users = users or {}
        self.config = config
        self._conn_ids = iter(range(1, 1 << 31))
        self._sock = socket.create_server((host, port))
        self.host, self.port = self._sock.getsockname()
        self._threads: list = []
        self._closing = False
        # a config'd server boots the placement driver's scheduling loop
        # (ref: PD runs beside the cluster; embedded here, so the server
        # owns its lifecycle). Config-less servers (tests) tick manually.
        if config is not None and getattr(self.store, "pd", None) is not None:
            self.store.pd.start_background(config.pd_tick_interval)

    def serve_forever(self):
        while not self._closing:
            try:
                sock, _ = self._sock.accept()
            except OSError:
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)  # as Go's net does under the reference
            t = threading.Thread(target=self._serve_conn, args=(sock,), daemon=True)
            t.start()
            self._threads.append(t)

    def start_background(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t

    def _serve_conn(self, sock):
        conn = Connection(sock, self, next(self._conn_ids))
        try:
            if conn.handshake():
                conn.run()
        except (ConnectionError, OSError):
            pass
        finally:
            conn.session.close()  # a transaction left open lets go of its locks
            try:
                sock.close()
            except OSError:
                pass

    def close(self):
        self._closing = True
        if getattr(self.store, "pd", None) is not None:
            self.store.pd.stop()
        try:
            self._sock.close()
        except OSError:
            pass
