// tmhash: native host-side SHA-256 Merkle engine.
//
// The framework's hashing hot plane lives on the TPU
// (tendermint_tpu/ops/merkle.py); this library is the HOST runtime
// counterpart for CPU-only nodes and small batches where device
// dispatch would lose: batched leaf hashing and reference-shaped tree
// roots ((n+1)/2 split, 0x00/0x01 domain separation — must match
// tendermint_tpu/types/merkle.py bit for bit), threaded across
// independent trees.  Bound into Python via ctypes
// (tendermint_tpu/utils/nativelib.py); no pybind11 dependency.
//
// Reference analog: the pure-Go merkle/part hashing the sync loop pays
// per block (reference types/part_set.go:95-122, types/tx.go:29-43).

#include <cstdint>
#include <cstring>
#include <atomic>
#include <functional>
#include <thread>
#include <vector>

#include "sha256.h"

namespace {

void prefixed_hash(uint8_t prefix, const uint8_t* a, size_t alen,
                   const uint8_t* b, size_t blen, uint8_t out[32]) {
  Sha256 s;
  s.update(&prefix, 1);
  s.update(a, alen);
  if (b) s.update(b, blen);
  s.final(out);
}

// reference-shaped tree over precomputed leaf hashes [n][32] (scratch
// must hold n*32 bytes); writes the root to out.
void tree_root(uint8_t* hashes, size_t n, uint8_t* out) {
  if (n == 0) {  // empty tree: sha256("") — matches the host merkle.root
    Sha256 s;
    s.final(out);
    return;
  }
  // plain recursion on the (n+1)/2 split; depth <= log2(n) + 1
  struct Rec {
    uint8_t* hs;
    void run(size_t lo, size_t hi, uint8_t out[32]) {
      if (hi - lo == 1) {
        std::memcpy(out, hs + lo * 32, 32);
        return;
      }
      size_t k = (hi - lo + 1) / 2;
      uint8_t l[32], r[32];
      run(lo, lo + k, l);
      run(lo + k, hi, r);
      prefixed_hash(0x01, l, 32, r, 32, out);
    }
  } rec{hashes};
  rec.run(0, n, out);
}

void run_threaded(size_t jobs, unsigned threads,
                  const std::function<void(size_t)>& fn) {
  if (threads <= 1 || jobs <= 1) {
    for (size_t i = 0; i < jobs; i++) fn(i);
    return;
  }
  std::vector<std::thread> ts;
  std::atomic<size_t> next{0};
  for (unsigned t = 0; t < threads; t++)
    ts.emplace_back([&] {
      for (size_t i = next.fetch_add(1); i < jobs; i = next.fetch_add(1))
        fn(i);
    });
  for (auto& t : ts) t.join();
}

}  // namespace

extern "C" {

// n equal-length messages, 0x00 leaf prefix -> [n][32] digests
void tm_leaf_hashes(const uint8_t* in, uint64_t n, uint64_t len,
                    uint8_t* out, uint32_t threads) {
  run_threaded(n == 0 ? 0 : 1 + (n - 1) / 1024, threads, [&](size_t chunk) {
    size_t lo = chunk * 1024, hi = lo + 1024 < n ? lo + 1024 : n;
    for (size_t i = lo; i < hi; i++)
      prefixed_hash(0x00, in + i * len, len, nullptr, 0, out + i * 32);
  });
}

// t trees x n equal-length leaves each -> [t][32] roots
void tm_merkle_roots(const uint8_t* leaves, uint64_t t, uint64_t n,
                     uint64_t leaf_len, uint8_t* roots, uint32_t threads) {
  run_threaded(t, threads, [&](size_t ti) {
    std::vector<uint8_t> hs(n * 32);
    const uint8_t* base = leaves + ti * n * leaf_len;
    for (size_t i = 0; i < n; i++)
      prefixed_hash(0x00, base + i * leaf_len, leaf_len, nullptr, 0,
                    hs.data() + i * 32);
    tree_root(hs.data(), n, roots + ti * 32);
  });
}
}
