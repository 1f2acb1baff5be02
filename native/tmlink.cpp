// tmlink: the receive side of one peer link, a message a call.
//
// What `MConnection._recv_routine` does a packet in Python
// (tendermint_tpu/p2p/connection.py, over p2p/secret.py and
// p2p/transport.py), done here a MESSAGE a call, with the interpreter's
// lock released for the length of it (ctypes drops it): read the
// frame's length and the frame off the socket, check the truncated
// HMAC-SHA256 tag in constant time BEFORE any keystream is computed,
// XOR the SHA-256 counter keystream, advance `seq`, read MConnection
// packets out of the opened bytes, charge the token bucket and sleep
// for it, append a packet's payload to its channel's buffer.  The
// Python loop stays, for every link that is not a secret link straight
// over a socket, and as the plain reference the tests hold this one
// equal to (tests/test_link_native.py): the same wire bytes give the
// same messages, the same refusals, the same `seq`.
//
// Wire (all big-endian), as p2p/secret.py and p2p/connection.py have it:
//   frame   := len(u32) ciphertext[len - 16] tag[16],  16 <= len <= 1 MiB
//   keystream block ctr of frame seq := SHA-256(key || seq(u64) || ctr(u32))
//   tag     := HMAC-SHA256(mac_key, seq(u64) || ciphertext)[:16]
//   packet  := type(u8) body;  1 MSG: channel(u8) flags(u8) len(u16)
//              payload;  2 PING;  3 PONG;  flags bit0 = EOF
// Frames are not cut at packet boundaries: opened bytes are buffered.

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <memory>
#include <new>
#include <vector>

#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include "sha256.h"

namespace {

constexpr uint32_t MAX_FRAME = 1u << 20;  // SecretConnection.MAX_FRAME
constexpr size_t TAG_LEN = 16;
constexpr size_t RECV_SIZE = 1u << 16;    // transport._RECV_SIZE
constexpr uint8_t PKT_MSG = 1, PKT_PING = 2, PKT_PONG = 3, FLAG_EOF = 1;
// a return inside a message, so that the Python side's meters see a
// long one arrive: after this much payload, or this long in the call
constexpr uint64_t PROGRESS_BYTES = 1u << 16;
constexpr double PROGRESS_S = 0.1;
constexpr double SLEEP_SLICE_S = 0.05;    // the stop flag is looked at

// what a call returns; nativelib.LinkReceiver has the same numbers
enum : int32_t {
  EV_MSG = 1,          // a packet with EOF completed a message
  EV_PING = 2,         // a PING was read: a PONG is owed at once
  EV_PROGRESS = 3,     // inside a message: bytes to report, no more
  EV_STOPPED = 4,      // the stop flag was set
  EV_CLOSED = 5,       // the socket was shut down
  EV_OS_ERROR = 6,     // arg = errno
  EV_BAD_MAC = 7,
  EV_BAD_FRAME_LEN = 8,     // arg = the length
  EV_BAD_PACKET_TYPE = 9,   // arg = the type
  EV_UNKNOWN_CHANNEL = 10,  // ch = the id
  EV_OVER_CAPACITY = 11,    // ch = the id, arg = the capacity
};

double mono() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return double(ts.tv_sec) + 1e-9 * double(ts.tv_nsec);
}

// bytes appended at `end`, read from `pos`; no fill on growth
struct Fifo {
  std::vector<uint8_t> buf;
  size_t pos = 0, end = 0;

  size_t size() const { return end - pos; }
  const uint8_t* data() const { return buf.data() + pos; }
  void consume(size_t n) {
    pos += n;
    if (pos == end) pos = end = 0;
  }
  // room for n more bytes at the end; returns where they go
  uint8_t* room(size_t n) {
    if (end + n > buf.size()) {
      if (pos) {
        std::memmove(buf.data(), buf.data() + pos, end - pos);
        end -= pos;
        pos = 0;
      }
      if (end + n > buf.size()) buf.resize(end + n);
    }
    return buf.data() + end;
  }
  void append(const uint8_t* p, size_t n) {
    if (!n) return;
    std::memcpy(room(n), p, n);
    end += n;
  }
};

struct Channel {
  uint64_t capacity = 0;            // recv_message_capacity
  std::vector<uint8_t> msg;         // the message being reassembled
  std::atomic<uint64_t> arrived{0}; // its length, for other threads
};

}  // namespace

extern "C" {

struct TmLinkEvent {
  int32_t code;
  int32_t ch;
  uint64_t arg;
  const uint8_t* msg;   // EV_MSG: valid until the next call
  uint64_t msg_len;
  uint64_t bytes;       // charged to the limiter since the last return
};

}  // extern "C"

namespace {

struct Link {
  int fd = -1;                      // a dup of the socket's: ours to close
  uint8_t key[32];
  uint64_t seq = 0;
  Sha256 mac_inner, mac_outer;      // HMAC states after the key's block
  double rate = 0, burst = 0, tokens = 0, last = 0;
  std::atomic<int> stop{0};
  Fifo raw;                         // off the socket, still sealed
  Fifo opened;                      // opened frames, not yet packets
  std::unique_ptr<Channel> chans[256];
  Channel* handed = nullptr;        // its message went out with EV_MSG

  ~Link() {
    if (fd >= 0) close(fd);
  }

  void set_mac_key(const uint8_t k[32]) {
    uint8_t pad[64];
    for (int i = 0; i < 64; i++) pad[i] = (i < 32 ? k[i] : 0) ^ 0x36;
    mac_inner.reset();
    mac_inner.update(pad, 64);
    for (int i = 0; i < 64; i++) pad[i] = (i < 32 ? k[i] : 0) ^ 0x5c;
    mac_outer.reset();
    mac_outer.update(pad, 64);
  }

  void tag(const uint8_t* ct, size_t n, uint8_t out[32]) const {
    uint8_t s[8], d[32];
    for (int i = 0; i < 8; i++) s[i] = uint8_t(seq >> (56 - 8 * i));
    Sha256 h = mac_inner;
    h.update(s, 8);
    h.update(ct, n);
    h.final(d);
    h = mac_outer;
    h.update(d, 32);
    h.final(out);
  }

  // dst[i] = ct[i] ^ keystream[i]; a block is one compression of
  // key || seq || ctr and its padding, of which only ctr changes
  void xor_keystream(const uint8_t* ct, size_t n, uint8_t* dst) const {
    uint8_t block[64] = {};
    std::memcpy(block, key, 32);
    for (int i = 0; i < 8; i++) block[32 + i] = uint8_t(seq >> (56 - 8 * i));
    block[44] = 0x80;
    block[62] = uint8_t((44 * 8) >> 8);
    block[63] = uint8_t(44 * 8);
    Sha256 h;
    for (size_t off = 0, ctr = 0; off < n; off += 32, ctr++) {
      for (int i = 0; i < 4; i++) block[40 + i] = uint8_t(ctr >> (24 - 8 * i));
      h.reset();
      h.compress(block);
      size_t take = n - off < 32 ? n - off : 32;
      for (size_t i = 0; i < take; i++)
        dst[off + i] = ct[off + i] ^ uint8_t(h.h[i >> 2] >> (24 - 8 * (i & 3)));
    }
  }

  // at least `need` bytes in `raw`, or the event that says why not
  int32_t fill_raw(size_t need, uint64_t* arg) {
    while (raw.size() < need) {
      size_t want = need - raw.size();
      if (want < RECV_SIZE) want = RECV_SIZE;
      ssize_t got = recv(fd, raw.room(want), want, 0);
      if (got > 0) {
        raw.end += size_t(got);
      } else if (got == 0) {
        return EV_CLOSED;
      } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
        // a socket with a Python timeout is non-blocking underneath
        if (stop.load()) return EV_STOPPED;
        pollfd p{fd, POLLIN, 0};
        poll(&p, 1, int(1e3 * SLEEP_SLICE_S));
      } else if (errno != EINTR) {
        *arg = uint64_t(errno);
        return EV_OS_ERROR;
      }
    }
    return 0;
  }

  // one more frame off the socket into `opened`.  A refused frame is
  // consumed and `seq` stays: the frame that was due still opens.
  int32_t open_frame(uint64_t* arg) {
    int32_t ev = fill_raw(4, arg);
    if (ev) return ev;
    const uint8_t* p = raw.data();
    uint32_t n = (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
                 (uint32_t(p[2]) << 8) | uint32_t(p[3]);
    if (n < TAG_LEN || n > MAX_FRAME) {
      raw.consume(4);
      *arg = n;
      return EV_BAD_FRAME_LEN;
    }
    ev = fill_raw(4 + size_t(n), arg);
    if (ev) return ev;
    const uint8_t* ct = raw.data() + 4;
    size_t ct_len = n - TAG_LEN;
    uint8_t want[32];
    tag(ct, ct_len, want);
    uint8_t diff = 0;
    for (size_t i = 0; i < TAG_LEN; i++) diff |= want[i] ^ ct[ct_len + i];
    if (diff) {
      raw.consume(4 + size_t(n));
      return EV_BAD_MAC;
    }
    xor_keystream(ct, ct_len, opened.room(ct_len));
    opened.end += ct_len;
    seq++;
    raw.consume(4 + size_t(n));
    return 0;
  }

  int32_t need_opened(size_t n, uint64_t* arg) {
    while (opened.size() < n) {
      int32_t ev = open_frame(arg);
      if (ev) return ev;
    }
    return 0;
  }

  // _RateLimiter.consume: false when the stop flag ended the sleep
  bool charge(uint64_t n) {
    if (rate <= 0) return true;
    double now = mono();
    tokens += (now - last) * rate;
    if (tokens > burst) tokens = burst;
    last = now;
    tokens -= double(n);
    if (tokens >= 0) return true;
    double deadline = now + -tokens / rate;
    for (double left; (left = deadline - mono()) > 0;) {
      if (stop.load()) return false;
      if (left > SLEEP_SLICE_S) left = SLEEP_SLICE_S;
      timespec ts{time_t(left), long(1e9 * (left - double(time_t(left))))};
      nanosleep(&ts, nullptr);
    }
    return true;
  }

  int32_t run(TmLinkEvent* ev) {
    if (handed) {
      handed->msg.clear();
      handed = nullptr;
    }
    const double t_enter = mono();
    uint64_t payload = 0;
    for (;;) {
      if (stop.load()) return EV_STOPPED;
      int32_t e = need_opened(1, &ev->arg);
      if (e) return e;
      uint8_t type = opened.data()[0];
      opened.consume(1);
      if (type == PKT_PING) return EV_PING;
      if (type == PKT_PONG) continue;
      if (type != PKT_MSG) {
        ev->arg = type;
        return EV_BAD_PACKET_TYPE;
      }
      if ((e = need_opened(4, &ev->arg))) return e;
      const uint8_t* h = opened.data();
      uint8_t ch_id = h[0], flags = h[1];
      size_t len = (size_t(h[2]) << 8) | h[3];
      opened.consume(4);
      if ((e = need_opened(len, &ev->arg))) return e;
      // in the Python loop's order: the limiter and the meter first,
      // then the channel, the capacity, the EOF
      bool awake = charge(5 + len);
      ev->bytes += 5 + len;
      ev->ch = ch_id;
      Channel* ch = chans[ch_id].get();
      if (!ch) {
        opened.consume(len);
        return EV_UNKNOWN_CHANNEL;
      }
      ch->msg.insert(ch->msg.end(), opened.data(), opened.data() + len);
      opened.consume(len);
      ch->arrived.store(ch->msg.size());
      if (ch->msg.size() > ch->capacity) {
        ev->arg = ch->capacity;
        return EV_OVER_CAPACITY;
      }
      if (flags & FLAG_EOF) {
        ev->msg = ch->msg.data();
        ev->msg_len = ch->msg.size();
        ch->arrived.store(0);
        handed = ch;
        return EV_MSG;
      }
      if (!awake) return EV_STOPPED;
      payload += len;
      if (payload >= PROGRESS_BYTES || mono() - t_enter >= PROGRESS_S)
        return EV_PROGRESS;
    }
  }
};

}  // namespace

extern "C" {

// the receive state of a link over socket `fd` (dup'ed: the caller's
// stays the caller's); nullptr when it cannot be made
void* tm_link_new(int fd, const uint8_t key[32], const uint8_t mac_key[32],
                  uint64_t seq, double rate, double burst) {
  Link* l = new (std::nothrow) Link;
  if (!l) return nullptr;
  l->fd = dup(fd);
  if (l->fd < 0) {
    delete l;
    return nullptr;
  }
  std::memcpy(l->key, key, 32);
  l->set_mac_key(mac_key);
  l->seq = seq;
  l->rate = rate;
  l->burst = l->tokens = burst;
  l->last = mono();
  return l;
}

void tm_link_add_channel(void* link, uint8_t id, uint64_t capacity) {
  Link* l = static_cast<Link*>(link);
  if (!l->chans[id]) l->chans[id].reset(new Channel);
  l->chans[id]->capacity = capacity;
}

// what the Python readers held at the hand-over: sealed bytes off the
// socket, and opened bytes no packet had taken yet
void tm_link_feed(void* link, const uint8_t* raw, uint64_t n_raw,
                  const uint8_t* opened, uint64_t n_opened) {
  Link* l = static_cast<Link*>(link);
  l->raw.append(raw, n_raw);
  l->opened.append(opened, n_opened);
}

// blocks until there is something to say; one caller at a time
int32_t tm_link_recv(void* link, TmLinkEvent* ev) {
  *ev = TmLinkEvent{};
  ev->code = static_cast<Link*>(link)->run(ev);
  return ev->code;
}

// bytes arrived of the message now being received on `id`; any thread
uint64_t tm_link_receiving(void* link, uint8_t id) {
  Channel* ch = static_cast<Link*>(link)->chans[id].get();
  return ch ? ch->arrived.load() : 0;
}

uint64_t tm_link_seq(void* link) { return static_cast<Link*>(link)->seq; }

// the receive routine has ended: the socket's dup goes now, the state
// when nothing can ask `tm_link_receiving` any more (tm_link_free)
void tm_link_close(void* link) {
  Link* l = static_cast<Link*>(link);
  if (l->fd >= 0) close(l->fd);
  l->fd = -1;
}

// ends a sleep in the limiter; a blocked recv ends when the socket is
// shut down, which the caller does next.  Any thread.
void tm_link_stop(void* link) { static_cast<Link*>(link)->stop.store(1); }

void tm_link_free(void* link) { delete static_cast<Link*>(link); }

}  // extern "C"
