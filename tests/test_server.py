"""MySQL wire protocol server + client (ref: pkg/server/conn.go handshake,
dispatch, writeResultSet; validated over a real TCP socket with the
framework's own text-protocol client)."""

import socket
import struct

import pytest

from tidb_tpu.server import MiniClient, MySQLServer, split_statements
from tidb_tpu.server import protocol as P
from tidb_tpu.server.client import ClientError
from tidb_tpu.server.server import Connection, column_flags, datum_text
from tidb_tpu.sql import Session


@pytest.fixture(scope="module")
def server():
    srv = MySQLServer(port=0)
    srv.start_background()
    yield srv
    srv.close()


@pytest.fixture()
def client(server):
    c = MiniClient(server.host, server.port)
    yield c
    c.close()


def test_handshake_and_ping(client):
    assert client.ping()


def test_ddl_dml_select(client):
    assert client.query("CREATE TABLE st (id INT PRIMARY KEY, name VARCHAR(20), v INT)") == 0
    assert client.query("INSERT INTO st VALUES (1,'ann',10),(2,'bob',20)") == 2
    cols, rows = client.query("SELECT id, name, v FROM st ORDER BY id")
    assert cols == ["id", "name", "v"]
    assert rows == [["1", "ann", "10"], ["2", "bob", "20"]]


def test_null_and_expressions(client):
    client.query("CREATE TABLE sn (id INT PRIMARY KEY, x INT)")
    client.query("INSERT INTO sn VALUES (1, NULL), (2, 5)")
    cols, rows = client.query("SELECT x, x + 1 FROM sn ORDER BY id")
    assert rows == [[None, None], ["5", "6"]]


def test_aggregate_over_wire(client):
    client.query("CREATE TABLE sa (id INT PRIMARY KEY, v INT)")
    client.query("INSERT INTO sa VALUES (1,1),(2,2),(3,3)")
    cols, rows = client.query("SELECT count(*), sum(v), avg(v) FROM sa")
    assert rows[0][0] == "3"
    assert rows[0][1] == "6"


def test_error_packet(client):
    with pytest.raises(ClientError) as ei:
        client.query("SELECT * FROM no_such_table")
    assert "no_such_table" in str(ei.value)


def test_multi_statement(client):
    client.query("CREATE TABLE sm (id INT PRIMARY KEY)")
    got = client.query("INSERT INTO sm VALUES (1); INSERT INTO sm VALUES (2); SELECT count(*) FROM sm")
    assert got == (["count(*)"], [["2"]]) or got[1] == [["2"]]


def test_transactions_over_wire(server):
    c1 = MiniClient(server.host, server.port)
    c2 = MiniClient(server.host, server.port)
    try:
        c1.query("CREATE TABLE stx (id INT PRIMARY KEY, v INT)")
        c1.query("INSERT INTO stx VALUES (1, 10)")
        c1.query("BEGIN")
        c1.query("UPDATE stx SET v = 99 WHERE id = 1")
        _, rows = c2.query("SELECT v FROM stx")
        assert rows == [["10"]], "other connection must not see uncommitted write"
        c1.query("COMMIT")
        _, rows = c2.query("SELECT v FROM stx")
        assert rows == [["99"]]
    finally:
        c1.close()
        c2.close()


def test_auth_rejected():
    srv = MySQLServer(port=0, users={"alice": b"secret"})
    srv.start_background()
    try:
        with pytest.raises(ClientError):
            MiniClient(srv.host, srv.port, user="mallory", password="nope")
        c = MiniClient(srv.host, srv.port, user="alice", password="secret")
        assert c.ping()
        c.close()
        with pytest.raises(ClientError):
            MiniClient(srv.host, srv.port, user="alice", password="wrong")
    finally:
        srv.close()


def test_split_statements():
    assert split_statements("a; b;c") == ["a", "b", "c"]
    assert split_statements("insert into t values (';');") == ["insert into t values (';')"]
    assert split_statements('select ";;" ; x') == ['select ";;"', "x"]
    assert split_statements("select 1") == ["select 1"]


# ------------------------------------------------------------ buffered packet I/O
def frame(payload: bytes, seq: int) -> bytes:
    return struct.pack("<I", len(payload))[:3] + bytes([seq]) + payload


def per_packet_bytes(packets: list) -> bytes:
    """What the writer of one `sendall` a packet put on the wire for a
    command's reply: a header and a payload each, sequence ids from 1."""
    return b"".join(frame(p, seq) for seq, p in enumerate(packets, start=1))


def result_packets(res, more: bool = False) -> list:
    """One statement's result as `write_result` always framed it, from
    protocol.py's encoders (autocommit, outside a transaction)."""
    status = P.SERVER_STATUS_AUTOCOMMIT | (Connection.SERVER_MORE_RESULTS if more else 0)
    if not res.columns:
        return [P.ok_packet(affected=res.affected, status=status)]
    out = [P.lenenc_int(len(res.columns))]
    for name, ft in zip(res.columns, res.fts):
        out.append(P.column_def(str(name), int(ft.tp), ft.flen, max(ft.decimal, 0), column_flags(ft)))
    out.append(P.eof_packet(status))
    out.extend(P.text_row([datum_text(d) for d in row]) for row in res.rows)
    out.append(P.eof_packet(status))
    return out


def raw_reply(client, sql: str) -> bytes:
    """One COM_QUERY written to the client's socket beside its PacketIO,
    and every byte the server answers until it falls silent."""
    sock = client.sock
    sock.sendall(frame(bytes([P.COM_QUERY]) + sql.encode(), 0))
    got = sock.recv(1 << 16)  # the reply's first bytes, under the client's timeout
    sock.settimeout(0.3)
    try:
        while part := sock.recv(1 << 16):
            got += part
    except socket.timeout:
        pass
    finally:
        sock.settimeout(10.0)
    return got


GOLDEN_SELECT = "SELECT a, b, c FROM gold WHERE a <= 4 ORDER BY a"


@pytest.mark.parametrize("case", ["select", "insert", "error", "two_statements", "error_after_result"])
def test_reply_bytes_are_those_of_the_per_packet_writer(server, client, case):
    client.query("DROP TABLE IF EXISTS gold")
    client.query("CREATE TABLE gold (a BIGINT PRIMARY KEY, b VARCHAR(10), c DECIMAL(8,2))")
    client.query("INSERT INTO gold VALUES (1,'x',1.50),(2,NULL,2.25),(3,'zz',NULL),(4,'',4.00),(5,'q',5.00)")
    oracle = Session(server.store, server.catalog)  # the same store, no wire

    def err(sql: str) -> bytes:
        with pytest.raises(Exception) as ei:  # noqa: PT011 - whatever the statement raises, the wire carries
            oracle.execute(sql)
        return P.err_packet(getattr(ei.value, "code", 1105), str(ei.value))

    if case == "select":
        got = raw_reply(client, GOLDEN_SELECT)
        want = result_packets(oracle.execute(GOLDEN_SELECT))
        assert len(want) == 1 + 3 + 1 + 4 + 1
    elif case == "insert":
        got = raw_reply(client, "INSERT INTO gold VALUES (9,'n',9.99)")
        want = [P.ok_packet(affected=1)]
        assert got == b"\x07\x00\x00\x01" + b"\x00\x01\x00\x02\x00\x00\x00"
    elif case == "error":
        got = raw_reply(client, "SELECT * FROM no_such_gold")
        want = [err("SELECT * FROM no_such_gold")]
        assert got[3] == 1 and got[4] == 0xFF
    elif case == "two_statements":
        got = raw_reply(client, "INSERT INTO gold VALUES (7,'m',7.00); " + GOLDEN_SELECT.replace("4", "7"))
        want = [P.ok_packet(affected=1, status=P.SERVER_STATUS_AUTOCOMMIT | Connection.SERVER_MORE_RESULTS)]
        want += result_packets(oracle.execute(GOLDEN_SELECT.replace("4", "7")))
        assert len(want) == 1 + (1 + 3 + 1 + 6 + 1)  # one run of sequence ids over both results
    else:  # the ERR leaves after the packets that the first statement buffered
        got = raw_reply(client, GOLDEN_SELECT + "; SELECT * FROM no_such_gold")
        want = result_packets(oracle.execute(GOLDEN_SELECT), more=True) + [err("SELECT * FROM no_such_gold")]
    assert got == per_packet_bytes(want)
    assert client.ping()  # the connection reads on from a clean packet boundary


def test_two_commands_in_one_segment_are_both_answered(client):
    """What the reader received past one packet stays for the next read."""
    ping = frame(bytes([P.COM_PING]), 0)
    query = frame(bytes([P.COM_QUERY]) + b"SELECT 41 + 1", 0)
    client.sock.sendall(ping + query)
    assert client.io.read()[0] == 0x00
    assert client._read_result() == (["41 + 1"], [["42"]])
    assert client.ping()


def test_result_larger_than_the_buffers_arrives_whole(client):
    client.query("CREATE TABLE big (id INT PRIMARY KEY, c CHAR(120))")
    client.query("INSERT INTO big VALUES " + ",".join(f"({i},'{str(1000 + i) * 30}')" for i in range(1, 401)))
    recvs = client.io.recvs
    _, rows = client.query("SELECT id, c FROM big ORDER BY id")
    assert rows == [[str(i), str(1000 + i) * 30] for i in range(1, 401)]
    assert sum(len(r[1]) for r in rows) > 2 * P.FLUSH_BYTES
    assert client.io.recvs - recvs >= 2  # more than one receive could hold


@pytest.mark.parametrize("sent", [b"\x0a\x00", b"\x0a\x00\x00\x00abc"], ids=["mid_header", "mid_body"])
def test_peer_closing_mid_packet_raises(sent):
    a, b = socket.socketpair()
    try:
        a.sendall(frame(b"whole", 0) + sent)
        a.close()
        io = P.PacketIO(b)
        assert io.read() == b"whole"
        with pytest.raises(ConnectionError):
            io.read()
    finally:
        b.close()


def test_failed_auth_delivers_err_1045_before_the_close():
    srv = MySQLServer(port=0, users={"alice": b"secret"})
    srv.start_background()
    sock = socket.create_connection((srv.host, srv.port), timeout=10)
    try:
        io = P.PacketIO(sock)
        assert io.read()[0] == 10  # HandshakeV10
        caps = P.CLIENT_PROTOCOL_41 | P.CLIENT_SECURE_CONNECTION | P.CLIENT_PLUGIN_AUTH
        io.write(struct.pack("<IIB", caps, 1 << 24, P.CHARSET_UTF8MB4) + b"\x00" * 23
                 + b"mallory\x00" + b"\x00" + b"mysql_native_password\x00")
        io.flush()
        reply = io.read()
        assert reply[0] == 0xFF and struct.unpack_from("<H", reply, 1)[0] == 1045
        assert sock.recv(1) == b""  # and only then the server closed
    finally:
        sock.close()
        srv.close()
