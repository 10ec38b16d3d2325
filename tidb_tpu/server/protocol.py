"""MySQL client/server wire protocol codec (ref: pkg/server/conn.go packet
IO + handshake, pkg/server/column.go column definitions, and the protocol
constants in pkg/parser/mysql/const.go).

Covers what a standard client needs to connect and run queries:
  - packet framing: 3-byte little-endian length + 1-byte sequence id
  - HandshakeV10 greeting, HandshakeResponse41 parsing
  - mysql_native_password auth (SHA1 scramble check; empty password OK)
  - OK / ERR / EOF packets (CLIENT_PROTOCOL_41 shapes)
  - column definition 41 + text-protocol result rows (length-encoded)

I/O is buffered in both directions (ref: the bufio reader and writer of
pkg/server/internal/packetio.go).  `PacketIO.write` frames a packet into an
output buffer, and `flush` hands the buffer to the socket in one send; the
buffer flushes itself once it holds `FLUSH_BYTES`.  Whoever is about to wait
for the peer flushes first: the server once per command (server.py:
`Connection.handle_query` after the last statement's reply, `Connection.run`
for every other command and for error replies, the handshake before each
read), the client after each command it writes (client.py).  `PacketIO.read`
takes packets out of an input buffer that one `recv` of `RECV_BYTES`
refills; bytes past the packet stay for the next read.
"""

from __future__ import annotations

import hashlib
import os
import struct

# capability flags (ref: mysql/const.go Client*)
CLIENT_LONG_PASSWORD = 1 << 0
CLIENT_FOUND_ROWS = 1 << 1
CLIENT_LONG_FLAG = 1 << 2
CLIENT_CONNECT_WITH_DB = 1 << 3
CLIENT_PROTOCOL_41 = 1 << 9
CLIENT_TRANSACTIONS = 1 << 13
CLIENT_SECURE_CONNECTION = 1 << 15
CLIENT_MULTI_STATEMENTS = 1 << 16
CLIENT_MULTI_RESULTS = 1 << 17
CLIENT_PLUGIN_AUTH = 1 << 19
CLIENT_DEPRECATE_EOF = 1 << 24

SERVER_CAPS = (
    CLIENT_LONG_PASSWORD | CLIENT_FOUND_ROWS | CLIENT_LONG_FLAG
    | CLIENT_CONNECT_WITH_DB | CLIENT_PROTOCOL_41 | CLIENT_TRANSACTIONS
    | CLIENT_SECURE_CONNECTION | CLIENT_MULTI_STATEMENTS
    | CLIENT_MULTI_RESULTS | CLIENT_PLUGIN_AUTH
)

SERVER_STATUS_AUTOCOMMIT = 0x0002
SERVER_STATUS_IN_TRANS = 0x0001

# commands (ref: mysql/const.go Com*)
COM_QUIT = 0x01
COM_INIT_DB = 0x02
COM_QUERY = 0x03
COM_FIELD_LIST = 0x04
COM_PING = 0x0E
COM_STMT_PREPARE = 0x16
COM_STMT_EXECUTE = 0x17
COM_STMT_CLOSE = 0x19

CHARSET_UTF8MB4 = 255  # utf8mb4_0900_ai_ci


# the reference's buffered reader and writer hold 16 KiB each; a 100-row
# sysbench range reply is 13 KiB and leaves in one send
FLUSH_BYTES = 16 * 1024   # the output buffer flushes itself once it holds this much
RECV_BYTES = 16 * 1024    # what one recv asks the socket for


class PacketIO:
    """Framed, buffered packet reader/writer over a socket (ref: conn.go
    readPacket / writePacket over packetio.go's bufio pair; sequence ids
    reset per command).  Written packets wait in the output buffer until
    `flush`, or until the buffer passes `FLUSH_BYTES`."""

    def __init__(self, sock):
        self.sock = sock
        self.seq = 0
        # since the connection opened
        self.packets_out = 0  # packets written
        self.bytes_out = 0    # their bytes, headers included
        self.sends = 0        # socket sends that carried them
        self.recvs = 0        # socket receives
        self._out = bytearray()
        self._in = b""        # received and not yet read: _in[_pos:]
        self._pos = 0

    def reset(self):
        self.seq = 0

    def read(self) -> bytes:
        header = self._take(4)
        self.seq = (header[3] + 1) & 0xFF
        return self._take(header[0] | header[1] << 8 | header[2] << 16)

    def write(self, payload: bytes):
        out = self._out
        while True:  # a payload of 16 MB or more goes out as several packets
            chunk, payload = payload[: 0xFFFFFF], payload[0xFFFFFF:]
            out += (len(chunk) | self.seq << 24).to_bytes(4, "little")
            out += chunk
            self.seq = (self.seq + 1) & 0xFF
            self.packets_out += 1
            self.bytes_out += 4 + len(chunk)
            if len(out) >= FLUSH_BYTES:
                self.flush()
            if len(chunk) < 0xFFFFFF:
                break

    def flush(self):
        """Hand what was written to the socket, in one send."""
        if self._out:
            self.sock.sendall(self._out)
            self.sends += 1
            self._out.clear()

    def _take(self, n: int) -> bytes:
        """The next `n` received bytes; what one receive brought beyond
        them stays for the next call."""
        pos = self._pos
        if len(self._in) - pos < n:
            parts = [self._in[pos:]]
            have = len(parts[0])
            while have < n:
                part = self.sock.recv(RECV_BYTES)
                if not part:
                    raise ConnectionError("peer closed")
                self.recvs += 1
                parts.append(part)
                have += len(part)
            self._in, pos = b"".join(parts), 0
        self._pos = pos + n
        return self._in[pos : pos + n]


# ---------------------------------------------------------------- lenenc

def lenenc_int(v: int) -> bytes:
    if v < 251:
        return bytes([v])
    if v < 1 << 16:
        return b"\xfc" + struct.pack("<H", v)
    if v < 1 << 24:
        return b"\xfd" + struct.pack("<I", v)[:3]
    return b"\xfe" + struct.pack("<Q", v)


def lenenc_str(s: bytes) -> bytes:
    return lenenc_int(len(s)) + s


def read_lenenc_int(buf: bytes, pos: int) -> tuple[int, int]:
    first = buf[pos]
    if first < 251:
        return first, pos + 1
    if first == 0xFC:
        return struct.unpack_from("<H", buf, pos + 1)[0], pos + 3
    if first == 0xFD:
        return buf[pos + 1] | buf[pos + 2] << 8 | buf[pos + 3] << 16, pos + 4
    return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9


def read_lenenc_str(buf: bytes, pos: int) -> tuple[bytes, int]:
    n, pos = read_lenenc_int(buf, pos)
    return buf[pos : pos + n], pos + n


# ---------------------------------------------------------------- packets

def handshake_v10(conn_id: int, salt: bytes, version: str = "8.0.11-tidb-tpu") -> bytes:
    """Initial greeting (ref: conn.go writeInitialHandshake)."""
    out = bytes([10]) + version.encode() + b"\x00"
    out += struct.pack("<I", conn_id)
    out += salt[:8] + b"\x00"
    out += struct.pack("<H", SERVER_CAPS & 0xFFFF)
    out += bytes([CHARSET_UTF8MB4])
    out += struct.pack("<H", SERVER_STATUS_AUTOCOMMIT)
    out += struct.pack("<H", (SERVER_CAPS >> 16) & 0xFFFF)
    out += bytes([21])  # auth plugin data length
    out += b"\x00" * 10
    out += salt[8:20] + b"\x00"
    out += b"mysql_native_password\x00"
    return out


def parse_handshake_response(payload: bytes) -> dict:
    """HandshakeResponse41 (ref: conn.go readOptionalSSLRequestAndHandshakeResponse)."""
    caps, _max_packet, _charset = struct.unpack_from("<IIB", payload, 0)
    pos = 4 + 4 + 1 + 23
    end = payload.index(b"\x00", pos)
    user = payload[pos:end].decode()
    pos = end + 1
    if caps & CLIENT_PLUGIN_AUTH or caps & CLIENT_SECURE_CONNECTION:
        alen = payload[pos]
        auth = payload[pos + 1 : pos + 1 + alen]
        pos += 1 + alen
    else:
        end = payload.index(b"\x00", pos)
        auth = payload[pos:end]
        pos = end + 1
    db = ""
    if caps & CLIENT_CONNECT_WITH_DB and pos < len(payload):
        end = payload.index(b"\x00", pos)
        db = payload[pos:end].decode()
        pos = end + 1
    return {"caps": caps, "user": user, "auth": auth, "db": db}


def native_password_scramble(password: bytes, salt: bytes) -> bytes:
    """mysql_native_password: SHA1(pw) XOR SHA1(salt + SHA1(SHA1(pw)))."""
    if not password:
        return b""
    h1 = hashlib.sha1(password).digest()
    h2 = hashlib.sha1(h1).digest()
    mix = hashlib.sha1(salt + h2).digest()
    return bytes(a ^ b for a, b in zip(h1, mix))


def check_auth(stored_password: bytes, salt: bytes, client_auth: bytes) -> bool:
    if not stored_password:
        return client_auth in (b"", None) or client_auth == native_password_scramble(b"", salt)
    return client_auth == native_password_scramble(stored_password, salt)


def ok_packet(affected: int = 0, last_insert_id: int = 0, status: int = SERVER_STATUS_AUTOCOMMIT,
              warnings: int = 0) -> bytes:
    return (b"\x00" + lenenc_int(affected) + lenenc_int(last_insert_id)
            + struct.pack("<HH", status, warnings))


def err_packet(code: int, message: str, state: str = "HY000") -> bytes:
    return (b"\xff" + struct.pack("<H", code) + b"#" + state.encode()[:5].ljust(5, b"0")
            + message.encode())


def eof_packet(status: int = SERVER_STATUS_AUTOCOMMIT, warnings: int = 0) -> bytes:
    return b"\xfe" + struct.pack("<HH", warnings, status)


def column_def(name: str, tp: int, flen: int = 0, decimals: int = 0, flags: int = 0,
               charset: int = CHARSET_UTF8MB4) -> bytes:
    """ColumnDefinition41 (ref: pkg/server/column.go Dump)."""
    out = lenenc_str(b"def")  # catalog
    out += lenenc_str(b"")  # schema
    out += lenenc_str(b"")  # table
    out += lenenc_str(b"")  # org_table
    out += lenenc_str(name.encode())
    out += lenenc_str(name.encode())  # org_name
    out += bytes([0x0C])  # fixed-length fields size
    out += struct.pack("<H", charset)
    out += struct.pack("<I", max(flen, 0) or 255)
    out += bytes([tp & 0xFF])
    out += struct.pack("<H", flags)
    out += bytes([decimals])
    out += b"\x00\x00"
    return out


def text_row(values: list) -> bytes:
    """values: list of str|None (ref: pkg/server/util.go dumpTextRow)."""
    out = b""
    for v in values:
        if v is None:
            out += b"\xfb"
        else:
            out += lenenc_str(str(v).encode())
    return out


def new_salt() -> bytes:
    # 20 bytes, no zero bytes (clients c-string them)
    raw = bytearray(os.urandom(20))
    for i, b in enumerate(raw):
        if b == 0 or b == ord("$"):
            raw[i] = b + 1
    return bytes(raw)
