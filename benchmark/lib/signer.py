"""One signing worker of the chain builder: OpenSSL ed25519 and nothing else.

Reads frames from stdin, writes frames to stdout, ends when stdin closes
(so it cannot outlive the source child that started it).  A frame's first
byte says what it is: `KEYS` and the concatenated 32-byte seeds this
worker signs for from now on (answered `ok`; a churning chain sends one
per set, and a key once made is kept), or `SIGN` and one message,
answered by the concatenated 64-byte signatures of all its keys, in
order.  The `cryptography` binding holds the GIL while
it signs (measured: a thread pool of 8 signs 100 messages no faster than
one thread), so the builder fans a height's signatures out to processes.
"""

import struct
import sys

from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PrivateKey


KEYS, SIGN = b"k", b"m"


def keys_for(key_of: dict, seeds: list[bytes]) -> list[Ed25519PrivateKey]:
    """The key objects of `seeds`, in order; each is made once and kept
    in `key_of` (a chain's sets share most of their members)."""
    for s in seeds:
        if s not in key_of:
            key_of[s] = Ed25519PrivateKey.from_private_bytes(s)
    return [key_of[s] for s in seeds]


def read_frame(f):
    head = f.read(4)
    if len(head) < 4:
        return None
    (n,) = struct.unpack(">I", head)
    data = f.read(n)
    return data if len(data) == n else None


def write_frame(f, data: bytes) -> None:
    f.write(struct.pack(">I", len(data)) + data)
    f.flush()


def main() -> int:
    fin, fout = sys.stdin.buffer, sys.stdout.buffer
    key_of: dict[bytes, Ed25519PrivateKey] = {}
    keys: list[Ed25519PrivateKey] = []
    while True:
        frame = read_frame(fin)
        if frame is None:
            return 0
        kind, body = frame[:1], frame[1:]
        if kind == KEYS:
            keys = keys_for(key_of, [body[i:i + 32]
                                     for i in range(0, len(body), 32)])
            write_frame(fout, b"ok")
        elif kind == SIGN:
            write_frame(fout, b"".join(k.sign(body) for k in keys))
        else:
            return 1


if __name__ == "__main__":
    sys.exit(main())
