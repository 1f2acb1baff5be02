"""One signing worker of the chain builder: OpenSSL ed25519 and nothing else.

Reads frames from stdin, writes frames to stdout, ends when stdin closes
(so it cannot outlive the source child that started it).  The first frame
is the concatenated 32-byte seeds this worker signs for; every later
frame is one message, answered by the concatenated 64-byte signatures of
all its keys, in order.  The `cryptography` binding holds the GIL while
it signs (measured: a thread pool of 8 signs 100 messages no faster than
one thread), so the builder fans a height's signatures out to processes.
"""

import struct
import sys

from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PrivateKey


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
    seeds = read_frame(fin)
    if seeds is None:
        return 1
    keys = [Ed25519PrivateKey.from_private_bytes(seeds[i:i + 32])
            for i in range(0, len(seeds), 32)]
    write_frame(fout, b"ok")
    while True:
        msg = read_frame(fin)
        if msg is None:
            return 0
        write_frame(fout, b"".join(k.sign(msg) for k in keys))


if __name__ == "__main__":
    sys.exit(main())
