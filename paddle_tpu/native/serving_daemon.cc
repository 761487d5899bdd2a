// paddle_tpu_serving: Python-free C++ serving daemon (ISSUE 10 / r15).
//
// The piece the reference capi never had: a standalone HTTP daemon over
// the native execution backends —
//
//   * shared-parameter multi-threaded sessions: one immutable engine,
//     N worker threads serving POST /v1/infer concurrently (the
//     paddle/capi/examples/model_inference/multi_thread analog: every
//     session references the SAME parameter storage, no duplication);
//   * a decode request queue with CONTINUOUS BATCHING: the decode loop
//     owns a fixed array of hypothesis slots and ticks them together;
//     when a slot goes dead mid-loop (its hypothesis finished — the r8
//     early-exit signal) the next queued request is admitted into the
//     freed slot instead of draining the whole batch, so a stream of
//     concurrent users decodes at high slot occupancy (Orca-style
//     iteration-level scheduling; --drain_batch flips back to classic
//     static batching for A/B benches);
//   * /metrics in the r9 observability registry's Prometheus text
//     exposition (paddle_serving_* family, docs/observability.md) and
//     /healthz.
//
// Execution backends (--backend):
//   interp  the in-process Python-free graph interpreter
//           (infer_engine.cc): dense / ids+mask bundles, ldd-clean on
//           any host. Default when the bundle's layer set is covered.
//   pjrt    the n-ary PJRT runner (pjrt_runner.cc): compiles the
//           bundle's exported StableHLO module (signature-driven typed
//           args/results) on a real PJRT plugin — libtpu.so on a TPU
//           host. Always compiled in (the PJRT C API header is
//           vendored under third_party/; the build fails without it).
//   toy     a deterministic built-in decode model (no bundle needed):
//           every tick runs a real [slots,H]x[H,H] matmul (the fixed
//           per-tick cost of a compiled decode step, independent of how
//           many slots are live) and emits tokens by a splitmix-style
//           hash of (src digest, t) that tests/bench reproduce exactly.
//           This is the scheduler-verification backend: continuous-
//           batching wins are a property of the SCHEDULER, not of the
//           model math.
//
// HTTP surface (JSON in/out, Connection: close):
//   GET  /healthz        -> liveness (503 once the watchdog sees a
//                           decode tick stuck past --tick_hang_ms)
//   GET  /readyz         -> readiness (503 while draining after SIGTERM)
//   GET  /metrics        -> Prometheus text format 0.0.4
//   GET  /v1/signature   -> the bundle's recorded input/output signature
//   POST /v1/infer       -> {"inputs": {name: nested-array, ...}}
//   POST /v1/decode      -> {"src": [ids...], "max_new": N,
//                            "deadline_ms": D}   (or X-Deadline-Ms hdr)
//   POST /v1/reload      -> {"bundle": path}  zero-downtime parameter
//                           hot-swap: loads a second immutable engine,
//                           validates crc + signature against the live
//                           one, pointer-flips sessions between requests
//                           (SIGHUP re-reads the current --bundle path)
//   POST /v1/rows        -> {"delta": path}  streamed row freshness for
//                           host-resident tables (meta.host_tables):
//                           applies a PTPUDLT1 row delta onto the live
//                           bundle's mmap-backed row store when its
//                           base_version extends the live lineage and
//                           delta_seq advances; torn/regressing deltas
//                           409 with the store untouched
//
// Production hardening (ISSUE 11, docs/serving.md "Operating the
// daemon"): per-request deadlines swept from the queue AND from live
// slots (504, slot freed for re-admission), load shed above a queue
// high-water mark (503 + Retry-After), graceful SIGTERM drain (finish
// every admitted request within --drain_timeout_s, then ordered
// teardown — join workers, join scheduler, exit 0; no _exit), request
// body cap (413), slow-client I/O timeout (408), and deterministic
// fault injection via PTPU_SERVING_FAULTS (mirrors distributed/
// faults.py: "point@at[xcount][:ms]" joined by ';' — points tick.slow,
// backend.error, reload.torn) driving tests/test_serving_chaos.py and
// tools/chaos_sweep.py --serving.
//
// Build: make -C paddle_tpu/native serving; self-contained smoke:
// ./paddle_tpu_serving --selftest (spawns itself on a free port, POSTs
// requests, scrapes /metrics — the `make serve-smoke` target).

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <signal.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cctype>
#include <chrono>
#include <cmath>
#include <condition_variable>
#include <cstdlib>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <functional>
#include <list>
#include <map>
#include <memory>
#include <mutex>
#include <set>
#include <sstream>
#include <string>
#include <thread>
#include <vector>

#include "bundle_util.h"
#include "infer_engine.h"

namespace {

using Clock = std::chrono::steady_clock;
using ptpu::JParser;
using ptpu::JValue;

double now_s() {
  return std::chrono::duration<double>(Clock::now().time_since_epoch())
      .count();
}

int64_t now_us() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             Clock::now().time_since_epoch())
      .count();
}

// --- metrics registry (r9 exposition format, native twin) -----------------
//
// Mirrors observability/metrics.py's Prometheus text form: # HELP/# TYPE
// headers, histogram as _bucket{le=}/_sum/_count with cumulative counts.

struct Metrics {
  std::mutex mu;
  // insertion-ordered series
  struct Entry {
    std::string type, help;
    std::vector<std::pair<std::string, double>> series;  // label-str -> v
    // histogram storage
    std::vector<double> buckets;
    std::map<std::string, std::vector<int64_t>> hcounts;
    std::map<std::string, double> hsum;
    std::map<std::string, int64_t> hcount;
  };
  std::vector<std::string> order;
  std::map<std::string, Entry> entries;

  Entry& reg(const std::string& name, const char* type, const char* help) {
    auto it = entries.find(name);
    if (it == entries.end()) {
      order.push_back(name);
      Entry& e = entries[name];
      e.type = type;
      e.help = help;
      return e;
    }
    return it->second;
  }

  void add(const std::string& name, double v, const char* help,
           const std::string& labels = "") {
    std::lock_guard<std::mutex> l(mu);
    Entry& e = reg(name, "counter", help);
    for (auto& kv : e.series)
      if (kv.first == labels) { kv.second += v; return; }
    e.series.push_back({labels, v});
  }

  void set(const std::string& name, double v, const char* help,
           const std::string& labels = "") {
    std::lock_guard<std::mutex> l(mu);
    Entry& e = reg(name, "gauge", help);
    for (auto& kv : e.series)
      if (kv.first == labels) { kv.second = v; return; }
    e.series.push_back({labels, v});
  }

  void observe(const std::string& name, double v, const char* help,
               const std::string& labels = "") {
    observe_buckets(name, v, help, {}, labels);
  }

  // Histogram with caller-chosen bucket bounds, fixed on the FIRST
  // observation of the family (later calls reuse the registered
  // bounds). Empty = the r9 log-spaced latency ladder. The /metrics
  // and /metrics.json shapes are unchanged, so metrics_dump.py's
  // quantile math round-trips custom bounds like the default ones.
  void observe_buckets(const std::string& name, double v, const char* help,
                       const std::vector<double>& buckets,
                       const std::string& labels = "") {
    std::lock_guard<std::mutex> l(mu);
    Entry& e = reg(name, "histogram", help);
    if (e.buckets.empty()) {
      if (!buckets.empty()) {
        e.buckets = buckets;
      } else {
        // fixed log-spaced latency buckets, 100us .. ~100s (r9 style)
        double b = 1e-4;
        for (int i = 0; i < 20; ++i) { e.buckets.push_back(b); b *= 2; }
      }
    }
    auto& c = e.hcounts[labels];
    if (c.empty()) c.assign(e.buckets.size() + 1, 0);
    size_t i = 0;
    while (i < e.buckets.size() && v > e.buckets[i]) ++i;
    c[i] += 1;
    e.hsum[labels] += v;
    e.hcount[labels] += 1;
  }

  static std::string fmt(double v) {
    char buf[64];
    // integral doubles print EXACTLY through the full double-exact
    // integer range (2^53): the publisher confirms reloads by
    // comparing the param_version gauge against a 64-bit
    // bundle_version — a %g fallback would truncate it and fail every
    // confirm (observed at versions >= the old 1e15 cutoff)
    // range check FIRST: double->int64 conversion outside int64 range
    // is UB, so the cast may only run once |v| is known small
    if (std::fabs(v) <= 9007199254740992.0 && v == int64_t(v))
      snprintf(buf, sizeof(buf), "%lld", (long long)v);
    else
      snprintf(buf, sizeof(buf), "%g", v);
    return buf;
  }

  // JSON twin of /metrics with the same shape as the Python registry's
  // to_json() (observability/metrics.py), so tools/metrics_dump.py
  // --url works against the daemon exactly like the train-side exporter
  std::string json_snapshot() {
    std::lock_guard<std::mutex> l(mu);
    std::string out = "{";
    bool first_e = true;
    for (const auto& name : order) {
      Entry& e = entries[name];
      if (!first_e) out += ",";
      first_e = false;
      out += "\"" + ptpu::json_escape(name) + "\":{\"type\":\"" + e.type +
             "\",\"help\":\"" + ptpu::json_escape(e.help) +
             "\",\"series\":{";
      bool first_s = true;
      if (e.type == "histogram") {
        for (auto& [labels, counts] : e.hcounts) {
          if (!first_s) out += ",";
          first_s = false;
          out += "\"" + ptpu::json_escape(labels) + "\":{\"buckets\":[";
          for (size_t i = 0; i < counts.size(); ++i)
            out += (i ? "," : "") + std::to_string(counts[i]);
          out += "],\"sum\":" + fmt(e.hsum[labels]) +
                 ",\"count\":" + std::to_string(e.hcount[labels]) + "}";
        }
        out += "},\"buckets\":[";
        for (size_t i = 0; i < e.buckets.size(); ++i)
          out += (i ? "," : "") + fmt(e.buckets[i]);
        out += "]}";
        continue;
      }
      for (auto& [labels, v] : e.series) {
        if (!first_s) out += ",";
        first_s = false;
        out += "\"" + ptpu::json_escape(labels) + "\":" + fmt(v);
      }
      out += "}}";
    }
    out += "}";
    return out;
  }

  std::string prometheus() {
    std::lock_guard<std::mutex> l(mu);
    std::string out;
    for (const auto& name : order) {
      Entry& e = entries[name];
      out += "# HELP " + name + " " + e.help + "\n";
      out += "# TYPE " + name + " " + e.type + "\n";
      if (e.type == "histogram") {
        for (auto& [labels, counts] : e.hcounts) {
          int64_t cum = 0;
          std::string lb = labels.empty() ? "" : labels + ",";
          for (size_t i = 0; i < e.buckets.size(); ++i) {
            cum += counts[i];
            out += name + "_bucket{" + lb + "le=\"" +
                   fmt(e.buckets[i]) + "\"} " + std::to_string(cum) + "\n";
          }
          cum += counts.back();
          out += name + "_bucket{" + lb + "le=\"+Inf\"} " +
                 std::to_string(cum) + "\n";
          std::string sfx = labels.empty() ? "" : "{" + labels + "}";
          out += name + "_sum" + sfx + " " + fmt(e.hsum[labels]) + "\n";
          out += name + "_count" + sfx + " " +
                 std::to_string(e.hcount[labels]) + "\n";
        }
      } else {
        for (auto& [labels, v] : e.series) {
          std::string sfx = labels.empty() ? "" : "{" + labels + "}";
          out += name + sfx + " " + fmt(v) + "\n";
        }
      }
    }
    return out;
  }
};

Metrics g_metrics;

// --- deterministic fault injection ----------------------------------------
//
// The native twin of distributed/faults.py: each injection point counts
// its triggers, and PTPU_SERVING_FAULTS scripts faults at exact trigger
// ordinals so a chaos run is a pure function of (plan, workload).
// Spec grammar (';'-joined): point@at[xcount][:ms] — e.g.
//   PTPU_SERVING_FAULTS="tick.slow@3x2:500;reload.torn@1"
// fires a 500 ms stall on decode ticks 3 and 4 and tears the first
// reload's bundle read. Points: tick.slow (stall the scheduler tick —
// what the watchdog must catch), backend.error (the compiled step
// fails: every live hypothesis errors with 500), reload.torn (the new
// bundle's bytes arrive truncated — crc validation must reject it),
// batch.window (stall an infer gather window before it executes —
// gathered requests whose deadline expires inside the stall must 504
// individually without stalling the rest of the batch).

struct FaultSpec {
  std::string point;
  int at = 1, count = 1;
  double ms = 0;
};

struct Faults {
  std::vector<FaultSpec> specs;
  std::mutex mu;
  std::map<std::string, int> counters;

  void parse(const char* env) {
    if (env == nullptr || *env == '\0') return;
    std::string s(env);
    size_t pos = 0;
    while (pos <= s.size()) {
      size_t semi = s.find(';', pos);
      std::string tok = s.substr(
          pos, semi == std::string::npos ? std::string::npos : semi - pos);
      pos = semi == std::string::npos ? s.size() + 1 : semi + 1;
      if (tok.empty()) continue;
      FaultSpec f;
      size_t at = tok.find('@');
      f.point = tok.substr(0, at);
      if (at != std::string::npos) {
        std::string rest = tok.substr(at + 1);
        size_t colon = rest.find(':');
        if (colon != std::string::npos) {
          f.ms = atof(rest.c_str() + colon + 1);
          rest = rest.substr(0, colon);
        }
        size_t x = rest.find('x');
        if (x != std::string::npos) {
          f.count = atoi(rest.c_str() + x + 1);
          rest = rest.substr(0, x);
        }
        f.at = atoi(rest.c_str());
      }
      if (f.at < 1) f.at = 1;
      if (f.count < 1) f.count = 1;
      specs.push_back(f);
    }
  }

  // Count one trigger of `point`; returns the spec firing at this
  // ordinal (pointer stays valid: specs are immutable after parse).
  const FaultSpec* fire(const char* point) {
    if (specs.empty()) return nullptr;
    std::lock_guard<std::mutex> l(mu);
    int n = ++counters[point];
    for (const auto& f : specs)
      if (f.point == point && f.at <= n && n < f.at + f.count) {
        g_metrics.add("paddle_serving_faults_injected_total", 1,
                      "deterministic injected faults (PTPU_SERVING_FAULTS)",
                      std::string("point=\"") + point + "\"");
        return &f;
      }
    return nullptr;
  }
};

Faults g_faults;

// PJRT execute — and runner creation during a hot-swap — serialized
// per PROCESS, not per bundle: during a reload overlap, requests
// holding the old bundle snapshot and requests on the new one target
// the same device, and two concurrent executes (or a create racing an
// execute) is exactly what this mutex has always prevented.
std::mutex g_pjrt_device_mu;

// --- host-resident row store (meta.host_tables) ----------------------------
//
// The serving twin of host_table.py's PTPUROWS sidecar: the bundle
// file is mmap'd read-only and rows are addressed IN PLACE, so a
// 100M-row table costs evictable page-cache pages, never a resident
// [V, D] tensor. Per-request staging gathers only the request's
// touched ids through a bounded LRU row cache (--host_cache_rows),
// and POST /v1/rows lays versioned row deltas over the mapped base
// between full publishes (the overlay wins over both the sidecar and
// the LRU; a full reload builds fresh stores, clearing the delta
// tail). Block crcs are validated lazily on first touch — a cold
// start never pays a full [V, D] checksum pass.

inline uint32_t rd_u32(const uint8_t* p) {
  uint32_t v;
  memcpy(&v, p, 4);
  return v;
}

inline uint64_t rd_u64(const uint8_t* p) {
  uint64_t v;
  memcpy(&v, p, 8);
  return v;
}

struct HostRowStore {
  // bundle meta.host_tables record
  std::string table, entry;
  int64_t vocab = 0, width = 0, block_rows = 4096;
  bool dense_src = false;               // meta "dense" (sidecar is the
                                        // full 0..V-1 prefix)
  std::vector<std::string> feeds;       // claimed id data-layer names

  // mmap'd bundle + sidecar layout (absolute file offsets)
  int fd = -1;
  const uint8_t* map = nullptr;
  size_t map_len = 0;
  size_t ids_off = 0, data_off = 0, crc_off = 0;
  int64_t n_rows = 0;
  bool contiguous = false;

  // runtime state, all under mu
  mutable std::mutex mu;
  mutable std::vector<uint8_t> block_state;  // 0 unchecked / 1 ok / 2 bad
  size_t cache_cap = 65536;                  // --host_cache_rows
  struct CacheRow {
    std::vector<float> v;
    std::list<int64_t>::iterator lru_it;
  };
  mutable std::list<int64_t> lru;            // front = hottest
  mutable std::map<int64_t, CacheRow> cache;
  std::map<int64_t, std::vector<float>> overlay;  // /v1/rows deltas win
  int64_t delta_seq = 0;                     // last applied delta
  mutable int64_t lookups = 0, hits = 0;

  ~HostRowStore() {
    if (map != nullptr)
      munmap(const_cast<uint8_t*>(reinterpret_cast<const uint8_t*>(map)),
             map_len);
    if (fd >= 0) close(fd);
  }

  // Map `path` and validate the PTPUROWS header at [off, off + len).
  // Non-empty return = the load error (fail closed).
  std::string open_map(const std::string& path, size_t off, size_t len) {
    auto bad = [&](const std::string& why) {
      return "host table '" + table + "': " + why;
    };
    fd = open(path.c_str(), O_RDONLY);
    if (fd < 0) return bad("cannot open bundle " + path);
    struct stat sb;
    if (fstat(fd, &sb) != 0) return bad("fstat failed");
    map_len = size_t(sb.st_size);
    void* m = mmap(nullptr, map_len, PROT_READ, MAP_SHARED, fd, 0);
    if (m == MAP_FAILED) {
      map_len = 0;
      return bad("mmap failed");
    }
    map = static_cast<const uint8_t*>(m);
    if (len < 48 || off + len > map_len)
      return bad("rows sidecar out of bundle bounds (torn write?)");
    const uint8_t* h = map + off;
    if (memcmp(h, "PTPUROWS", 8) != 0)
      return bad("bad rows sidecar magic");
    if (ptpu::crc32(h, 44) != rd_u32(h + 44))
      return bad("rows sidecar header crc mismatch (torn or corrupt)");
    if (rd_u32(h + 8) != 1)
      return bad("unsupported rows sidecar version " +
                 std::to_string(rd_u32(h + 8)));
    int64_t w = int64_t(rd_u32(h + 12));
    int64_t v = int64_t(rd_u64(h + 16));
    n_rows = int64_t(rd_u64(h + 24));
    int64_t brows = int64_t(rd_u32(h + 32));
    uint32_t flags = rd_u32(h + 36);
    contiguous = (flags & 1) != 0;
    if (w != width || v != vocab || brows != block_rows)
      return bad("sidecar header disagrees with bundle meta (width " +
                 std::to_string(w) + " vs " + std::to_string(width) +
                 ", vocab " + std::to_string(v) + " vs " +
                 std::to_string(vocab) + ", block_rows " +
                 std::to_string(brows) + " vs " +
                 std::to_string(block_rows) + ")");
    size_t ids_len = contiguous ? 0 : size_t(n_rows) * 8;
    int64_t n_blocks =
        n_rows > 0 ? (n_rows + block_rows - 1) / block_rows : 0;
    if (48 + ids_len + size_t(n_rows) * size_t(width) * 4 +
            size_t(n_blocks) * 4 != len)
      return bad("sidecar size mismatch (torn write?)");
    ids_off = off + 48;
    data_off = ids_off + ids_len;
    crc_off = data_off + size_t(n_rows) * size_t(width) * 4;
    if (!contiguous &&
        ptpu::crc32(map + ids_off, ids_len) != rd_u32(h + 40))
      return bad("id array crc mismatch (torn or corrupt)");
    block_state.assign(size_t(n_blocks), 0);
    return "";
  }

  // One row into out[width]; "" or a corruption error. Caller holds mu.
  std::string fetch_locked(int64_t id, float* out) {
    ++lookups;
    auto ov = overlay.find(id);
    if (ov != overlay.end()) {
      ++hits;
      memcpy(out, ov->second.data(), size_t(width) * 4);
      return "";
    }
    auto c = cache.find(id);
    if (c != cache.end()) {
      ++hits;
      lru.splice(lru.begin(), lru, c->second.lru_it);
      memcpy(out, c->second.v.data(), size_t(width) * 4);
      return "";
    }
    int64_t idx = -1;
    if (contiguous) {
      if (id >= 0 && id < n_rows) idx = id;
    } else {
      int64_t lo = 0, hi = n_rows;
      while (lo < hi) {
        int64_t mid = (lo + hi) / 2;
        if (int64_t(rd_u64(map + ids_off + size_t(mid) * 8)) < id)
          lo = mid + 1;
        else
          hi = mid;
      }
      if (lo < n_rows &&
          int64_t(rd_u64(map + ids_off + size_t(lo) * 8)) == id)
        idx = lo;
    }
    if (idx < 0) {
      // never-written id: zero row by the sidecar's "missing":"zero"
      // contract (the lazy trainer store's untouched-row semantics)
      memset(out, 0, size_t(width) * 4);
      return "";
    }
    int64_t b = idx / block_rows;
    if (block_state[size_t(b)] == 0) {
      size_t lo_b =
          data_off + size_t(b) * size_t(block_rows) * size_t(width) * 4;
      int64_t hi_row = std::min((b + 1) * block_rows, n_rows);
      size_t blen = size_t(hi_row - b * block_rows) * size_t(width) * 4;
      block_state[size_t(b)] =
          ptpu::crc32(map + lo_b, blen) == rd_u32(map + crc_off +
                                                  size_t(b) * 4)
              ? 1
              : 2;
    }
    if (block_state[size_t(b)] == 2)
      return "host table '" + table + "': row block " +
             std::to_string(b) + " crc mismatch (corrupt sidecar)";
    memcpy(out, map + data_off + size_t(idx) * size_t(width) * 4,
           size_t(width) * 4);
    if (cache_cap > 0) {
      lru.push_front(id);
      CacheRow cr;
      cr.v.assign(out, out + width);
      cr.lru_it = lru.begin();
      cache.emplace(id, std::move(cr));
      while (cache.size() > cache_cap) {
        cache.erase(lru.back());
        lru.pop_back();
      }
    }
    return "";
  }

  std::string gather(const std::vector<int64_t>& ids, float* out) {
    std::lock_guard<std::mutex> l(mu);
    for (size_t i = 0; i < ids.size(); ++i) {
      std::string e = fetch_locked(ids[i], out + i * size_t(width));
      if (!e.empty()) return e;
    }
    return "";
  }

  // Apply a fully-validated delta: overlay rows win over both the
  // sidecar and any cached copy. Caller validated EVERYTHING first —
  // this never partially applies.
  void apply_rows(const std::vector<int64_t>& ids,
                  const std::vector<float>& rows, int64_t seq) {
    std::lock_guard<std::mutex> l(mu);
    for (size_t i = 0; i < ids.size(); ++i) {
      overlay[ids[i]].assign(rows.begin() + int64_t(i) * width,
                             rows.begin() + int64_t(i + 1) * width);
      auto c = cache.find(ids[i]);
      if (c != cache.end()) {
        lru.erase(c->second.lru_it);
        cache.erase(c);
      }
    }
    delta_seq = seq;
  }

  int64_t cur_delta_seq() const {
    std::lock_guard<std::mutex> l(mu);
    return delta_seq;
  }

  double hit_rate() const {
    std::lock_guard<std::mutex> l(mu);
    return lookups > 0 ? double(hits) / double(lookups) : 0.0;
  }

  double resident_bytes() const {
    std::lock_guard<std::mutex> l(mu);
    return double(cache.size() + overlay.size()) * double(width) * 4.0;
  }
};

// Parse + fully validate a PTPUDLT1 delta file's bytes
// (host_table.py write_row_delta). Everything is checked BEFORE any
// store mutation, so a torn delta 409s with the store untouched.
// Non-empty return = the rejection reason.
std::string parse_row_delta(const std::string& buf, std::string* table,
                            double* base_version, int64_t* delta_seq,
                            std::vector<int64_t>* ids,
                            std::vector<float>* rows, int64_t* width,
                            int64_t* vocab) {
  if (buf.size() < 16 || buf.compare(0, 8, "PTPUDLT1") != 0)
    return "not a PTPUDLT1 row delta";
  uint64_t jlen = 0;
  memcpy(&jlen, buf.data() + 8, 8);
  if (jlen > buf.size() || 16 + size_t(jlen) > buf.size())
    return "row delta truncated (torn write?)";
  JParser jp{buf.data() + 16, buf.data() + 16 + jlen};
  JValue hdr = jp.parse();
  if (!jp.ok) return "row delta header is not valid JSON";
  const JValue* t = hdr.get("table");
  const JValue* bv = hdr.get("base_version");
  const JValue* sq = hdr.get("delta_seq");
  const JValue* pc = hdr.get("payload_crc");
  if (t == nullptr || bv == nullptr || sq == nullptr || pc == nullptr)
    return "row delta header lacks table/base_version/delta_seq/"
           "payload_crc";
  *table = t->str;
  *base_version = bv->num;
  *delta_seq = int64_t(sq->num);
  const uint8_t* body =
      reinterpret_cast<const uint8_t*>(buf.data()) + 16 + size_t(jlen);
  size_t blen = buf.size() - 16 - size_t(jlen);
  char got[16];
  snprintf(got, sizeof(got), "%08x", ptpu::crc32(body, blen));
  if (pc->str != got)
    return "row delta payload crc mismatch (torn write?)";
  if (blen < 48 || memcmp(body, "PTPUROWS", 8) != 0)
    return "row delta payload is not a PTPUROWS section";
  if (ptpu::crc32(body, 44) != rd_u32(body + 44))
    return "row delta payload header crc mismatch";
  if (rd_u32(body + 8) != 1)
    return "unsupported row section version";
  *width = int64_t(rd_u32(body + 12));
  *vocab = int64_t(rd_u64(body + 16));
  int64_t n = int64_t(rd_u64(body + 24));
  int64_t brows = int64_t(rd_u32(body + 32));
  if (rd_u32(body + 36) & 1)
    return "row delta must carry an explicit id array";
  if (brows <= 0 || *width <= 0 || n < 0)
    return "row delta payload header is malformed";
  size_t ids_len = size_t(n) * 8;
  int64_t n_blocks = n > 0 ? (n + brows - 1) / brows : 0;
  if (48 + ids_len + size_t(n) * size_t(*width) * 4 +
          size_t(n_blocks) * 4 != blen)
    return "row delta payload size mismatch (torn write?)";
  if (ptpu::crc32(body + 48, ids_len) != rd_u32(body + 40))
    return "row delta id array crc mismatch";
  const uint8_t* data = body + 48 + ids_len;
  const uint8_t* crcs = data + size_t(n) * size_t(*width) * 4;
  for (int64_t b = 0; b < n_blocks; ++b) {
    size_t lo = size_t(b) * size_t(brows) * size_t(*width) * 4;
    size_t hi =
        size_t(std::min((b + 1) * brows, n)) * size_t(*width) * 4;
    if (ptpu::crc32(data + lo, hi - lo) != rd_u32(crcs + size_t(b) * 4))
      return "row delta block " + std::to_string(b) + " crc mismatch";
  }
  ids->resize(size_t(n));
  int64_t prev = -1;
  for (int64_t i = 0; i < n; ++i) {
    int64_t id = int64_t(rd_u64(body + 48 + size_t(i) * 8));
    if (id <= prev)
      return "row delta ids are not sorted unique non-negative";
    if (id >= *vocab)
      return "row delta id " + std::to_string(id) +
             " exceeds the declared vocab " + std::to_string(*vocab);
    (*ids)[size_t(i)] = id;
    prev = id;
  }
  rows->resize(size_t(n) * size_t(*width));
  memcpy(rows->data(), data, rows->size() * 4);
  return "";
}

// --- decode request + scheduler -------------------------------------------

// One flattened typed request feed (shared by /v1/infer and the bundle
// decode backends' per-request feeds).
struct Feed {
  std::string name;
  std::vector<int64_t> dims;
  std::vector<float> f32;
  std::vector<int32_t> i32;
  bool is_int = false;
};

struct DecodeReq {
  std::vector<int32_t> src;
  std::vector<Feed> feeds;  // bundle backends: per-request feed rows
                            // (the step init module's inputs, no slot
                            // dim); toy uses `src` only
  int max_new = 16;
  double deadline = 0;   // absolute now_s() bound; 0 = none. Expired
                         // requests are swept from the queue AND from
                         // live slots (freeing the slot) with a 504.
  bool stream = false;   // chunked token streaming: the handler sends
                         // each token as the tick emits it
  std::atomic<bool> cancelled{false};  // streaming client vanished
                                       // mid-decode (set by the handler
                                       // thread); the scheduler frees
                                       // the slot at the next round
  // result
  std::vector<int32_t> out_ids;   // streamed tokens, in emission order
  std::vector<int32_t> final_ids; // authoritative answer when the
                                  // backend distinguishes it (beam > 1:
                                  // the best hypothesis can change
                                  // between ticks, so streamed tokens
                                  // are provisional)
  bool has_final = false;
  int ticks = 0;
  bool continuous_admit = false;  // admitted while other slots were live
  std::string error;
  int http_status = 200;  // the error's HTTP mapping (504 deadline,
                          // 503 shutdown/shed, 500 backend failure)
  // sync — mu guards out_ids/final/done: the scheduler emits tokens
  // while a streaming handler drains them
  std::mutex mu;
  std::condition_variable cv;
  bool done = false;
  double t_enq = 0, t_start = 0, t_done = 0, t_first_token = 0;

  const std::vector<int32_t>& answer_ids() const {
    return has_final ? final_ids : out_ids;
  }

  void finish() {
    std::lock_guard<std::mutex> l(mu);
    t_done = now_s();
    done = true;
    cv.notify_all();
  }
  void wait() {
    std::unique_lock<std::mutex> l(mu);
    cv.wait(l, [&] { return done; });
  }
};

// Decode execution backend: owns per-slot model state. tick() runs the
// per-tick compute over the WHOLE slot array (the fixed cost of a
// compiled decode step) and emits tokens per live slot.
struct DecodeBackend {
  virtual ~DecodeBackend() = default;
  virtual int slots() const = 0;
  virtual void admit(int slot, const DecodeReq& r) = 0;
  virtual void retire(int slot) = 0;
  // (*emitted)[i] = tokens slot i produced THIS tick (usually 0 or 1;
  // the whole-loop drain fallback emits the full answer at once),
  // valid only where live[i]; (*dead)[i] set when slot i's request
  // finished THIS tick.
  virtual void tick(const std::vector<bool>& live,
                    std::vector<std::vector<int32_t>>* emitted,
                    std::vector<bool>* dead) = 0;
  // The authoritative final ids for a slot that just died (step
  // backend: best-beam row of the carry state, cut after eos). False =
  // the streamed tokens ARE the answer (toy backend).
  virtual bool final_ids(int /*slot*/, std::vector<int32_t>* /*out*/) {
    return false;
  }
  // True when the slot's request died because the BACKEND failed
  // (init/step execution error) — the scheduler answers 500 instead of
  // completing with empty or stale ids.
  virtual bool slot_failed(int /*slot*/) { return false; }
  // True when the backend can only decode batch-at-a-time (the
  // whole-loop fallback for bundles without step modules): the
  // scheduler forces drain mode.
  virtual bool requires_drain() const { return false; }
  // Validate/prepare a request for this backend (parse bundle feeds
  // etc.); non-empty return = 400 message. Toy accepts `src` as-is.
  virtual std::string prepare(DecodeReq* /*r*/) { return ""; }
};

// Deterministic toy decode model (see file header). Token rule (the
// tests reproduce it bit for bit in Python):
//   digest = fold(src):  d = (d * 1000003 + id) mod 2^64,  d0 = 0
//   gen_len(r) = digest % max_new + 1
//   token(t)   = ((digest ^ ((t+1) * 0x9E3779B97F4A7C15)) >> 17)
//                  % (vocab - 2) + 2
struct ToyBackend : DecodeBackend {
  int n_slots, hidden, vocab;
  int tick_us = 0;            // extra per-tick latency (bench/test knob:
                              // models a real chip's decode-step time)
  std::vector<float> W;       // [H, H]
  std::vector<float> h;       // [slots, H]
  std::vector<float> h2;
  std::vector<uint64_t> digest;
  std::vector<int> emitted_n, gen_len;

  ToyBackend(int slots_, int hidden_, int vocab_, int tick_us_ = 0)
      : n_slots(slots_), hidden(hidden_), vocab(vocab_),
        tick_us(tick_us_) {
    W.assign(size_t(hidden) * hidden, 0.0f);
    uint64_t s = 0x243F6A8885A308D3ull;
    for (auto& w : W) {
      s = s * 6364136223846793005ull + 1442695040888963407ull;
      w = float(int64_t(s >> 33) % 2048 - 1024) / 16384.0f;
    }
    h.assign(size_t(n_slots) * hidden, 0.0f);
    h2 = h;
    digest.assign(n_slots, 0);
    emitted_n.assign(n_slots, 0);
    gen_len.assign(n_slots, 0);
  }

  static uint64_t fold(const std::vector<int32_t>& src) {
    uint64_t d = 0;
    for (int32_t id : src) d = d * 1000003ull + uint64_t(uint32_t(id));
    return d;
  }

  int slots() const override { return n_slots; }

  std::string prepare(DecodeReq* r) override {
    return r->src.empty()
               ? "body wants {\"src\": [ids...], \"max_new\": n}"
               : "";
  }

  void admit(int slot, const DecodeReq& r) override {
    digest[slot] = fold(r.src);
    emitted_n[slot] = 0;
    gen_len[slot] = int(digest[slot] % uint64_t(r.max_new)) + 1;
    for (int i = 0; i < hidden; ++i)
      h[size_t(slot) * hidden + i] =
          float((digest[slot] >> (i % 48)) & 0xFF) / 256.0f;
  }

  void retire(int slot) override { digest[slot] = 0; }

  void tick(const std::vector<bool>& live,
            std::vector<std::vector<int32_t>>* emitted,
            std::vector<bool>* dead) override {
    // the fixed per-tick cost: one [slots,H] x [H,H] matmul + tanh over
    // EVERY slot, live or not — a compiled decode step does not shrink
    // when hypotheses die, which is exactly why recycling dead slots
    // (instead of draining) buys throughput
    for (int s = 0; s < n_slots; ++s) {
      const float* hs = h.data() + size_t(s) * hidden;
      float* ho = h2.data() + size_t(s) * hidden;
      for (int j = 0; j < hidden; ++j) {
        float acc = 0;
        const float* wc = W.data() + size_t(j) * hidden;
        for (int i = 0; i < hidden; ++i) acc += hs[i] * wc[i];
        ho[j] = std::tanh(acc);
      }
    }
    std::swap(h, h2);
    if (tick_us > 0)
      std::this_thread::sleep_for(std::chrono::microseconds(tick_us));
    emitted->assign(size_t(n_slots), {});
    dead->assign(size_t(n_slots), false);
    for (int s = 0; s < n_slots; ++s) {
      if (!live[s]) continue;
      uint64_t t = uint64_t(emitted_n[s]);
      uint64_t x = digest[s] ^ ((t + 1) * 0x9E3779B97F4A7C15ull);
      (*emitted)[s].push_back(int32_t((x >> 17) % uint64_t(vocab - 2)) + 2);
      emitted_n[s] += 1;
      if (emitted_n[s] >= gen_len[s]) (*dead)[s] = true;
    }
  }
};

struct Scheduler {
  std::unique_ptr<DecodeBackend> backend;
  bool drain_mode = false;
  size_t max_queue = 256;
  size_t high_water = 0;  // load-shed at this queue depth — the
                          // operator's admission-control knob. 0 =
                          // default to 3/4 max_queue at start(); set
                          // >= max_queue to make shedding unreachable
                          // (the hard queue-full 503 still applies)
  std::atomic<int64_t>* tick_busy_us = nullptr;  // watchdog heartbeat:
                          // now_us() while a backend tick runs, else 0

  std::mutex mu;
  std::condition_variable cv;
  std::deque<std::shared_ptr<DecodeReq>> queue;
  std::vector<std::shared_ptr<DecodeReq>> slot_req;
  std::atomic<bool> stop{false};
  std::atomic<bool> draining{false};  // graceful drain: no new submits,
                                      // queued + live work completes
  std::atomic<int> live_count{0};
  std::thread loop_thread;

  void start() {
    if (high_water == 0) high_water = max_queue * 3 / 4;
    // a backend that can only decode batch-at-a-time (the whole-loop
    // fallback) forces classic static batching
    if (backend->requires_drain()) drain_mode = true;
    slot_req.assign(size_t(backend->slots()), nullptr);
    loop_thread = std::thread([this] { loop(); });
  }

  // Destroying a joinable std::thread is std::terminate — early-exit
  // error paths (bad listen socket, failed stop pipe) must still tear
  // the loop down, not abort.
  ~Scheduler() { shutdown(); }

  // Hard stop: errors everything still queued or slotted with a 503 —
  // for graceful completion call begin_drain() and wait for idle()
  // first (the daemon's drain sequence does exactly that).
  void shutdown() {
    {
      // stop must flip under mu or the loop can check its wait
      // predicate, lose this notify, and never wake (lost-wakeup race)
      std::lock_guard<std::mutex> l(mu);
      stop = true;
    }
    cv.notify_all();
    if (loop_thread.joinable()) loop_thread.join();
  }

  void begin_drain() { draining = true; }

  // True when no request is queued or occupying a slot — the graceful
  // drain completion signal.
  bool idle() {
    std::lock_guard<std::mutex> l(mu);
    return queue.empty() && live_count.load() == 0;
  }

  enum SubmitResult { kOk, kShed, kFull, kShutdown };

  SubmitResult submit(const std::shared_ptr<DecodeReq>& r) {
    {
      std::lock_guard<std::mutex> l(mu);
      if (stop || draining) return kShutdown;
      if (queue.size() >= max_queue) return kFull;
      if (high_water > 0 && queue.size() >= high_water) return kShed;
      r->t_enq = now_s();
      queue.push_back(r);
      g_metrics.set("paddle_serving_queue_depth", double(queue.size()),
                    "decode requests waiting for a slot");
    }
    cv.notify_all();
    return kOk;
  }

  // Sweep expired AND client-cancelled requests: live slots first
  // (retire frees the slot for re-admission this very round), then the
  // queue. A streaming client that disconnected mid-decode marks its
  // request cancelled; the slot frees here at the NEXT tick — no
  // zombie carry state. Slots are only ever touched from the loop
  // thread; the queue needs mu.
  void sweep_deadlines(int S) {
    double now = now_s();
    for (int s = 0; s < S; ++s) {
      auto& r = slot_req[s];
      if (!r) continue;
      if (r->cancelled) {
        backend->retire(s);
        r->http_status = 499;      // nginx's client-closed-request
        r->error = "client disconnected mid-stream";
        g_metrics.add("paddle_serving_stream_disconnects_total", 1,
                      "streaming clients that vanished mid-decode "
                      "(their slot frees at the next tick)");
        r->finish();
        r = nullptr;
        continue;
      }
      if (r->deadline > 0 && now >= r->deadline) {
        backend->retire(s);
        r->http_status = 504;
        r->error = "deadline exceeded mid-decode";
        g_metrics.add("paddle_serving_deadline_exceeded_total", 1,
                      "requests expired past their deadline_ms",
                      "where=\"slot\"");
        r->finish();
        r = nullptr;
      }
    }
    std::lock_guard<std::mutex> l(mu);
    for (auto it = queue.begin(); it != queue.end();) {
      if ((*it)->cancelled) {
        (*it)->http_status = 499;
        (*it)->error = "client disconnected while queued";
        g_metrics.add("paddle_serving_stream_disconnects_total", 1,
                      "streaming clients that vanished mid-decode "
                      "(their slot frees at the next tick)");
        (*it)->finish();
        it = queue.erase(it);
        continue;
      }
      if ((*it)->deadline > 0 && now >= (*it)->deadline) {
        (*it)->http_status = 504;
        (*it)->error = "deadline exceeded while queued";
        g_metrics.add("paddle_serving_deadline_exceeded_total", 1,
                      "requests expired past their deadline_ms",
                      "where=\"queue\"");
        (*it)->finish();
        it = queue.erase(it);
      } else {
        ++it;
      }
    }
    g_metrics.set("paddle_serving_queue_depth", double(queue.size()),
                  "decode requests waiting for a slot");
  }

  void loop() {
    const int S = backend->slots();
    std::vector<bool> live(S, false), dead;
    std::vector<std::vector<int32_t>> emitted;
    while (!stop) {
      sweep_deadlines(S);
      int n_live = 0;
      for (int s = 0; s < S; ++s) n_live += slot_req[s] ? 1 : 0;
      live_count = n_live;
      // admission: continuous mode fills ANY free slot from the queue;
      // drain mode only admits into an all-idle batch (classic static
      // batching — the A/B baseline)
      {
        std::unique_lock<std::mutex> l(mu);
        if (n_live == 0 && queue.empty()) {
          cv.wait(l, [&] { return stop || !queue.empty(); });
          if (stop) break;
        }
        if (!drain_mode || n_live == 0) {
          // continuous-admission = joining a batch that was already
          // live at round entry; co-admissions that FORM a batch
          // together are ordinary static batching in both modes
          const int n_live_entry = n_live;
          for (int s = 0; s < S && !queue.empty(); ++s) {
            if (slot_req[s]) continue;
            auto r = queue.front();
            queue.pop_front();
            r->t_start = now_s();
            r->continuous_admit = n_live_entry > 0;
            slot_req[s] = r;
            backend->admit(s, *r);
            ++n_live;
            g_metrics.add("paddle_serving_decode_admitted_total", 1,
                          "requests admitted into a decode slot");
            g_metrics.add("paddle_serving_slot_admissions_total", 1,
                          "slot admissions by kind: fresh = into an "
                          "idle batch, mid_batch = into a slot freed "
                          "while other slots were still decoding",
                          r->continuous_admit ? "kind=\"mid_batch\""
                                              : "kind=\"fresh\"");
            if (r->continuous_admit)
              g_metrics.add("paddle_serving_admitted_inflight_total", 1,
                            "admissions into a freed slot while other "
                            "slots were still decoding (continuous "
                            "batching)");
          }
          g_metrics.set("paddle_serving_queue_depth", double(queue.size()),
                        "decode requests waiting for a slot");
        }
      }
      live_count = n_live;
      if (n_live == 0) continue;
      for (int s = 0; s < S; ++s) live[s] = slot_req[s] != nullptr;
      // the tick window: heartbeat for the watchdog, injected stalls
      // INSIDE it (a slow tick is exactly what the watchdog must see)
      if (tick_busy_us) tick_busy_us->store(now_us());
      if (const FaultSpec* f = g_faults.fire("tick.slow"))
        std::this_thread::sleep_for(
            std::chrono::microseconds(int64_t(f->ms * 1000)));
      if (g_faults.fire("backend.error") != nullptr) {
        // the compiled step failed: every live hypothesis is lost, the
        // slots free, and the daemon keeps serving (no wedge, no exit)
        for (int s = 0; s < S; ++s) {
          auto& r = slot_req[s];
          if (!r) continue;
          backend->retire(s);
          r->http_status = 500;
          r->error = "injected backend error";
          r->finish();
          r = nullptr;
        }
        live_count = 0;
        g_metrics.add("paddle_serving_backend_errors_total", 1,
                      "decode ticks lost to a backend failure");
        if (tick_busy_us) tick_busy_us->store(0);
        continue;
      }
      backend->tick(live, &emitted, &dead);
      if (tick_busy_us) tick_busy_us->store(0);
      g_metrics.add("paddle_serving_decode_ticks_total", 1,
                    "decode loop ticks executed");
      g_metrics.add("paddle_serving_decode_slot_live_ticks_total",
                    double(n_live),
                    "sum over ticks of live slots (occupancy numerator; "
                    "denominator = ticks * slots)");
      g_metrics.set("paddle_serving_slots_live", double(n_live),
                    "decode slots currently holding a request");
      bool any_finished = false;
      for (int s = 0; s < S; ++s) {
        if (!live[s]) continue;
        auto& r = slot_req[s];
        r->ticks += 1;
        if (!emitted[s].empty()) {
          // under r->mu: a streaming handler drains out_ids while we
          // append; it is woken per batch of tokens
          std::unique_lock<std::mutex> l(r->mu);
          for (int32_t tok : emitted[s]) r->out_ids.push_back(tok);
          bool first = r->t_first_token == 0;
          if (first) r->t_first_token = now_s();
          l.unlock();
          r->cv.notify_all();
          if (first)
            g_metrics.observe("paddle_serving_ttft_seconds",
                              r->t_first_token - r->t_enq,
                              "time to first token, enqueue to first "
                              "emitted token");
          g_metrics.add("paddle_serving_decode_tokens_total",
                        double(emitted[s].size()),
                        "tokens emitted across all slots");
        }
        if (dead[s]) {
          if (backend->slot_failed(s)) {
            // the compiled init/step failed for this slot: an explicit
            // 500, never a 200 with empty (or a previous request's)
            // ids
            r->http_status = 500;
            r->error = "decode backend failure";
            g_metrics.add("paddle_serving_errors_total", 1,
                          "request errors", "endpoint=\"decode\"");
          } else {
            std::vector<int32_t> fin;
            if (backend->final_ids(s, &fin)) {
              std::lock_guard<std::mutex> l(r->mu);
              r->final_ids = std::move(fin);
              r->has_final = true;
            }
          }
          backend->retire(s);
          g_metrics.observe("paddle_serving_request_seconds",
                            now_s() - r->t_enq,
                            "end-to-end request latency (enqueue to "
                            "completion)", "endpoint=\"decode\"");
          r->finish();
          r = nullptr;
          any_finished = true;
          g_metrics.add("paddle_serving_decode_completed_total", 1,
                        "decode requests completed");
        }
      }
      if (drain_mode && any_finished) {
        bool all_idle = true;
        for (int s = 0; s < S; ++s) all_idle = all_idle && !slot_req[s];
        if (all_idle)
          g_metrics.add("paddle_serving_batches_drained_total", 1,
                        "full batch drains (drain mode)");
      }
      if (any_finished) {
        int n = 0;
        for (int s = 0; s < S; ++s) n += slot_req[s] ? 1 : 0;
        live_count = n;
      }
    }
    // hard stop: everything still queued or slotted gets an explicit
    // 503 "shutting down" (the graceful path drains to idle() first,
    // so this tail only fires when --drain_timeout_s expired or the
    // stop was never meant to be graceful)
    std::lock_guard<std::mutex> l(mu);
    for (auto& r : slot_req)
      if (r) {
        r->http_status = 503;
        r->error = "daemon shutting down before decode finished";
        r->finish();
        r = nullptr;
      }
    while (!queue.empty()) {
      queue.front()->http_status = 503;
      queue.front()->error = "daemon shutting down before decode started";
      queue.front()->finish();
      queue.pop_front();
    }
    live_count = 0;
  }
};

// --- JSON <-> tensors ------------------------------------------------------

std::string json_emit(const JValue& v) {
  std::ostringstream o;
  switch (v.kind) {
    case JValue::kNull: o << "null"; break;
    case JValue::kBool: o << (v.b ? "true" : "false"); break;
    case JValue::kNum:
      if (v.num == int64_t(v.num) && std::fabs(v.num) < 1e15)
        o << int64_t(v.num);
      else
        o << v.num;
      break;
    case JValue::kStr: o << '"' << ptpu::json_escape(v.str) << '"'; break;
    case JValue::kArr: {
      o << '[';
      for (size_t i = 0; i < v.arr.size(); ++i)
        o << (i ? "," : "") << json_emit(v.arr[i]);
      o << ']';
      break;
    }
    case JValue::kObj: {
      o << '{';
      size_t i = 0;
      for (const auto& [k, val] : v.obj)
        o << (i++ ? "," : "") << '"' << ptpu::json_escape(k) << "\":"
          << json_emit(val);
      o << '}';
      break;
    }
  }
  return o.str();
}

// Flatten a nested JSON array into dims + doubles. Ragged -> error.
bool flatten_json(const JValue& v, std::vector<int64_t>* dims,
                  std::vector<double>* flat, int depth = 0) {
  if (v.kind == JValue::kNum) {
    if (depth == 0) return false;  // scalars must come nested
    flat->push_back(v.num);
    return true;
  }
  if (v.kind != JValue::kArr) return false;
  if (int(dims->size()) <= depth) dims->push_back(int64_t(v.arr.size()));
  else if ((*dims)[depth] != int64_t(v.arr.size())) return false;
  for (const auto& e : v.arr)
    if (!flatten_json(e, dims, flat, depth + 1)) return false;
  return true;
}

// --- the daemon ------------------------------------------------------------

struct FeedDef {
  std::string name;     // data layer name
  std::string kind;     // dense | index
  bool is_seq = false;

  bool operator==(const FeedDef& o) const {
    return name == o.name && kind == o.kind && is_seq == o.is_seq;
  }
};

struct SigIO {
  std::string name;
  int32_t dtype;
  std::vector<int64_t> dims;
};

// One immutable loaded bundle: engine handle(s) + the derived serving
// metadata. Sessions grab a shared_ptr snapshot per request, so a
// reload is a pointer flip — the old engine drains as its last
// in-flight request releases it, then frees here.
struct BundleState {
  ptpu_engine engine = nullptr;
  std::vector<FeedDef> feed_defs;
  std::vector<std::string> output_names;
  std::string signature_json;     // bundle meta.stablehlo.signature
                                  // (+ "step" sub-object when present)
  double version = 0;             // meta.bundle_version (io/merged_model)
  std::string crc;                // meta.param_crc32 (hex)
  // decode metadata (any build): whether the whole-loop module carries
  // generation outputs, and why the per-tick step export is absent
  // (meta.stablehlo_step_skip_reason) — the daemon logs the reason
  // when decode falls back to drain-batch whole-loop serving
  bool has_decode = false;
  std::string step_skip_reason;
  // quantization record (ISSUE 16): meta.quantize mode ('f32' when the
  // bundle carries none) + meta.param_bytes, folded into /v1/signature
  // and the paddle_serving_param_bytes{dtype} gauges
  std::string quant_mode = "f32";
  std::string quantize_json;       // meta.quantize, re-emitted JSON
  std::string param_bytes_json;    // meta.param_bytes, re-emitted JSON
  double param_bytes_total = 0;
  std::vector<std::pair<std::string, double>> param_bytes_by_dtype;
  // host-resident row tables (meta.host_tables): mmap'd sidecar stores,
  // one per table. The stores carry their own locks — requests holding
  // this const snapshot still gather rows and take deltas through them.
  // A reload swaps in FRESH stores (empty overlay, delta_seq 0): a full
  // publish supersedes and clears the streamed delta tail.
  std::map<std::string, std::shared_ptr<HostRowStore>> host_stores;
  std::string host_tables_json;    // meta.host_tables, re-emitted JSON
  // sig input names carrying role "host_rows" ([R, D] staged tables —
  // their leading dim is the row budget R, never the batch)
  std::set<std::string> host_row_inputs;
  void* pjrt = nullptr;           // ptpu_pjrt runner handle; all use
                                  // serialized under g_pjrt_device_mu
  std::vector<SigIO> sig_inputs, sig_outputs;
  int sig_static_batch = 0;
  // per-tick decode step programs (meta.stablehlo_step), compiled as
  // additional programs on the SAME pjrt runner/client
  int step_init_prog = -1, step_step_prog = -1;
  std::vector<SigIO> step_inputs, step_state, step_enc;
  int step_slots = 0, step_beam = 1, step_max_len = 0;
  int step_eos = 1;
  // batch-ladder forward programs (merge_model --export_batch_ladder):
  // (rung batch, program id) sorted by rung, compiled on the same
  // runner — the infer micro-batcher picks the smallest rung >= the
  // gathered row count and zero-pads up to it
  std::vector<std::pair<int, int>> ladder;

  ~BundleState() {
    if (engine != nullptr) ptpu_engine_destroy(engine);
    if (pjrt != nullptr) {
      // the drained old engine frees from whichever request thread
      // releases it last — possibly while the new runner executes
      std::lock_guard<std::mutex> l(g_pjrt_device_mu);
      ptpu_pjrt_destroy(pjrt);
    }
  }
};

// Map a decode request's feeds onto a bundle's recorded input specs:
// every init input needs a per-request row ({"inputs": {...}} form);
// the legacy {"src": [ids...]} form fills the FIRST i32 sequence feed
// (padded/truncated to the exported T) and its mask. Non-empty return
// = the 400 message.
std::string prepare_bundle_feeds(const std::vector<SigIO>& specs,
                                 DecodeReq* r) {
  if (!r->src.empty()) {
    for (const auto& io : specs) {
      bool is_mask = io.name.size() > 5 &&
          io.name.compare(io.name.size() - 5, 5, ":mask") == 0;
      if (io.dtype != PTPU_DT_I32 || io.dims.size() != 2 || is_mask)
        continue;
      bool already = false;
      for (const auto& f : r->feeds) already = already || f.name == io.name;
      if (already) break;
      int64_t T = io.dims[1];
      Feed v;
      v.name = io.name;
      v.is_int = true;
      v.dims = {T};
      for (int64_t j = 0; j < T; ++j)
        v.i32.push_back(j < int64_t(r->src.size()) ? r->src[size_t(j)]
                                                   : 0);
      Feed m;
      m.name = io.name + ":mask";
      m.dims = {T};
      for (int64_t j = 0; j < T; ++j)
        m.f32.push_back(j < int64_t(r->src.size()) ? 1.0f : 0.0f);
      r->feeds.push_back(std::move(v));
      r->feeds.push_back(std::move(m));
      break;
    }
  }
  for (const auto& io : specs) {
    if (io.dtype != PTPU_DT_I32 && io.dtype != PTPU_DT_F32)
      // fill_feed_row only marshals i32/f32 (all today's exporter
      // emits); anything else must refuse loudly, not corrupt rows
      return "decode input '" + io.name + "': unsupported feed dtype "
             "in the bundle signature (only i32/f32 rows are served)";
    int64_t elems = 1;
    for (int64_t d : io.dims) elems *= d;
    int64_t row = elems / std::max<int64_t>(
        io.dims.empty() ? 1 : io.dims[0], 1);
    const Feed* f = nullptr;
    for (const auto& c : r->feeds)
      if (c.name == io.name) f = &c;
    if (f == nullptr)
      return "decode request is missing input '" + io.name +
             "' (send {\"inputs\": {name: row, ...}}, or {\"src\": "
             "[ids...]} for single-sequence models)";
    int64_t got = int64_t(f->is_int ? f->i32.size() : f->f32.size());
    if (got != row)
      return "decode input '" + io.name + "': expected " +
             std::to_string(row) + " elements per request, got " +
             std::to_string(got);
  }
  return "";
}

// Shared sizing helpers for the bundle decode backends.
int64_t sig_elems(const SigIO& io) {
  int64_t e = 1;
  for (int64_t d : io.dims) e *= d;
  return e;
}

int64_t sig_isize(const SigIO& io) {
  return (io.dtype == PTPU_DT_I64 || io.dtype == PTPU_DT_F64) ? 8
         : (io.dtype == PTPU_DT_PRED || io.dtype == PTPU_DT_U8) ? 1
                                                                : 4;
}

void sig_tensor(ptpu_pjrt_tensor* t, const SigIO& io, void* data) {
  memset(t, 0, sizeof(*t));
  t->dtype = io.dtype;
  t->rank = int32_t(io.dims.size());
  for (size_t d = 0; d < io.dims.size(); ++d) t->dims[d] = io.dims[d];
  t->data = data;
  t->size_bytes = sig_elems(io) * sig_isize(io);
}

// Copy ONE slot row between equally-shaped [S, ...] buffers.
void copy_slot_row(std::vector<uint8_t>* dst,
                   const std::vector<uint8_t>& src, const SigIO& io,
                   int slot) {
  int64_t S = io.dims.empty() ? 1 : io.dims[0];
  size_t row = size_t(sig_elems(io) * sig_isize(io) / std::max<int64_t>(
      S, 1));
  memcpy(dst->data() + size_t(slot) * row,
         src.data() + size_t(slot) * row, row);
}

// Fill slot `slot` of an [S, ...]-shaped feed buffer from a request's
// per-row Feed (typed-converting to the spec dtype; missing elements
// zero) — the ONE row-marshalling implementation both bundle decode
// backends use.
void fill_feed_row(const SigIO& io, const std::vector<Feed>& feeds,
                   std::vector<uint8_t>* buf, int slot) {
  int64_t row = sig_elems(io) / std::max<int64_t>(
      io.dims.empty() ? 1 : io.dims[0], 1);
  const Feed* f = nullptr;
  for (const auto& c : feeds)
    if (c.name == io.name) f = &c;
  if (f == nullptr) return;
  uint8_t* dst = buf->data() + size_t(slot) * size_t(row * sig_isize(io));
  for (int64_t j = 0; j < row; ++j) {
    double v = f->is_int
                   ? (j < int64_t(f->i32.size()) ? f->i32[size_t(j)] : 0)
                   : (j < int64_t(f->f32.size()) ? f->f32[size_t(j)] : 0);
    if (io.dtype == PTPU_DT_I32)
      reinterpret_cast<int32_t*>(dst)[j] = int32_t(v);
    else
      reinterpret_cast<float*>(dst)[j] = float(v);
  }
}

// Continuous decode over the bundle's per-tick step modules
// (docs/serving.md "Step-module bundles"): the per-slot carry state —
// shaped by the recorded carry signature — lives in host buffers;
// admit() runs the `init` program with the new request's feeds placed
// in that slot's row (mid-decode; encoder rows are independent, so the
// other rows never touch this slot's state), and tick() executes the
// `step` program over the WHOLE slot array, live and free slots
// together (free slots are inert: counters capped at max_length,
// nothing alive). This is the real-model Orca-style iteration-level
// scheduler the toy backend only modeled. NOTE: exercised on hosts
// with a loadable PJRT plugin (libtpu.so); on plugin-less CI the
// Python twin paddle_tpu/step_decode.py pins the identical semantics.
struct StepBundleBackend : DecodeBackend {
  std::shared_ptr<const BundleState> B;   // pins programs + signature
  int S, beam, L, eos;
  std::vector<std::vector<uint8_t>> state_buf, enc_buf;
  std::vector<std::vector<uint8_t>> obufs;   // tick()'s persistent
                                             // output set; ping-pongs
                                             // with state_buf
  std::vector<std::vector<int32_t>> last_final;
  std::vector<bool> admit_failed;
  // per-slot request bound: the client's (capped) max_new — the step
  // module's own bound is the exported max_length, so shorter requests
  // are cut off scheduler-side (slot freed, answer truncated)
  std::vector<int> emitted_n, token_cap;
  int ids_idx = -1, scores_idx = -1, t_idx = -1;
  // newer step exports carry a per-slot max_new bound ("state:cap") in
  // the carry itself: a short-capped slot goes inert at ITS bound
  // inside the module, not just scheduler-side. Absent on older
  // bundles (cap_idx stays -1) — the scheduler-side cut still applies
  // either way, so both generations truncate identically.
  int cap_idx = -1;

  explicit StepBundleBackend(std::shared_ptr<const BundleState> b)
      : B(std::move(b)), S(B->step_slots), beam(B->step_beam),
        L(B->step_max_len), eos(B->step_eos) {
    state_buf.resize(B->step_state.size());
    for (size_t i = 0; i < B->step_state.size(); ++i) {
      const SigIO& io = B->step_state[i];
      state_buf[i].assign(size_t(sig_elems(io) * sig_isize(io)), 0);
      if (io.name == "state:ids") ids_idx = int(i);
      if (io.name == "state:scores") scores_idx = int(i);
      if (io.name == "state:t") t_idx = int(i);
      if (io.name == "state:cap") cap_idx = int(i);
    }
    // inert initial state: per-slot tick counters at max_length (the
    // capped fixpoint), nothing alive — free slots tick harmlessly
    if (t_idx >= 0) {
      int32_t* t =
          reinterpret_cast<int32_t*>(state_buf[size_t(t_idx)].data());
      for (int s = 0; s < S; ++s) t[s] = int32_t(L);
    }
    if (cap_idx >= 0) {
      int32_t* c =
          reinterpret_cast<int32_t*>(state_buf[size_t(cap_idx)].data());
      for (int s = 0; s < S; ++s) c[s] = int32_t(L);
    }
    enc_buf.resize(B->step_enc.size());
    for (size_t i = 0; i < B->step_enc.size(); ++i)
      enc_buf[i].assign(
          size_t(sig_elems(B->step_enc[i]) * sig_isize(B->step_enc[i])),
          0);
    last_final.assign(size_t(S), {});
    admit_failed.assign(size_t(S), false);
    emitted_n.assign(size_t(S), 0);
    token_cap.assign(size_t(S), 0);
  }

  int slots() const override { return S; }

  std::string prepare(DecodeReq* r) override {
    return prepare_bundle_feeds(B->step_inputs, r);
  }

  void admit(int slot, const DecodeReq& r) override {
    std::vector<std::vector<uint8_t>> bufs(B->step_inputs.size());
    std::vector<ptpu_pjrt_tensor> args(B->step_inputs.size());
    for (size_t i = 0; i < B->step_inputs.size(); ++i) {
      const SigIO& io = B->step_inputs[i];
      bufs[i].assign(size_t(sig_elems(io) * sig_isize(io)), 0);
      fill_feed_row(io, r.feeds, &bufs[i], slot);
      sig_tensor(&args[i], io, bufs[i].data());
    }
    // init results: state entries then enc entries (init_outputs order)
    size_t n_out = B->step_state.size() + B->step_enc.size();
    std::vector<std::vector<uint8_t>> obufs(n_out);
    std::vector<ptpu_pjrt_tensor> res(n_out);
    for (size_t i = 0; i < n_out; ++i) {
      const SigIO& io = i < B->step_state.size()
                            ? B->step_state[i]
                            : B->step_enc[i - B->step_state.size()];
      obufs[i].assign(size_t(sig_elems(io) * sig_isize(io)), 0);
      sig_tensor(&res[i], io, obufs[i].data());
    }
    int rc;
    {
      std::lock_guard<std::mutex> l(g_pjrt_device_mu);
      rc = ptpu_pjrt_execute_prog(B->pjrt, B->step_init_prog, args.data(),
                                  int32_t(args.size()), res.data(),
                                  int32_t(n_out));
    }
    if (rc != 0) {
      // the slot stays inert; tick() marks it dead and the scheduler
      // answers 500 (slot_failed) — never stale or empty 200 ids
      fprintf(stderr, "decode step init failed: %s\n",
              ptpu_pjrt_last_error());
      g_metrics.add("paddle_serving_backend_errors_total", 1,
                    "decode ticks lost to a backend failure");
      admit_failed[size_t(slot)] = true;
      last_final[size_t(slot)].clear();
      return;
    }
    admit_failed[size_t(slot)] = false;
    for (size_t i = 0; i < B->step_state.size(); ++i)
      copy_slot_row(&state_buf[i], obufs[i], B->step_state[i], slot);
    for (size_t i = 0; i < B->step_enc.size(); ++i)
      copy_slot_row(&enc_buf[i], obufs[B->step_state.size() + i],
                    B->step_enc[i], slot);
    last_final[size_t(slot)].clear();
    emitted_n[size_t(slot)] = 0;
    token_cap[size_t(slot)] = r.max_new > 0 ? r.max_new : L;
    // init emits cap = max_length (the uniform bound); the request's
    // own bound overwrites the slot row so the MODULE freezes this
    // slot at min(max_new, L) — not just the scheduler
    if (cap_idx >= 0)
      reinterpret_cast<int32_t*>(
          state_buf[size_t(cap_idx)].data())[slot] =
          int32_t(std::min(token_cap[size_t(slot)], L));
  }

  void retire(int slot) override {
    // nothing to free: an inert-or-overwritten row IS the free state;
    // force the counter to the capped fixpoint so a swept (deadline/
    // disconnect) slot stops evolving even though its hypotheses live
    if (t_idx >= 0)
      reinterpret_cast<int32_t*>(
          state_buf[size_t(t_idx)].data())[slot] = int32_t(L);
    if (cap_idx >= 0)
      reinterpret_cast<int32_t*>(
          state_buf[size_t(cap_idx)].data())[slot] = int32_t(L);
    admit_failed[size_t(slot)] = false;
  }

  void tick(const std::vector<bool>& live,
            std::vector<std::vector<int32_t>>* emitted,
            std::vector<bool>* dead) override {
    emitted->assign(size_t(S), {});
    dead->assign(size_t(S), false);
    size_t n_state = B->step_state.size(), n_enc = B->step_enc.size();
    std::vector<ptpu_pjrt_tensor> args(n_state + n_enc);
    for (size_t i = 0; i < n_state; ++i)
      sig_tensor(&args[i], B->step_state[i], state_buf[i].data());
    for (size_t i = 0; i < n_enc; ++i)
      sig_tensor(&args[n_state + i], B->step_enc[i], enc_buf[i].data());
    // step results: state' entries + emitted [S] i32 + done [S] i32.
    // The output buffer set persists across ticks and ping-pongs with
    // state_buf below — this is the per-token hot path, so no per-tick
    // allocation of the whole carry state.
    SigIO vec_io;
    vec_io.dtype = PTPU_DT_I32;
    vec_io.dims = {int64_t(S)};
    if (obufs.size() != n_state + 2) {
      obufs.resize(n_state + 2);
      for (size_t i = 0; i < n_state; ++i)
        obufs[i].assign(state_buf[i].size(), 0);
      for (size_t i = n_state; i < n_state + 2; ++i)
        obufs[i].assign(size_t(S) * 4, 0);
    }
    std::vector<ptpu_pjrt_tensor> res(n_state + 2);
    for (size_t i = 0; i < n_state; ++i)
      sig_tensor(&res[i], B->step_state[i], obufs[i].data());
    for (size_t i = n_state; i < n_state + 2; ++i)
      sig_tensor(&res[i], vec_io, obufs[i].data());
    int rc;
    {
      std::lock_guard<std::mutex> l(g_pjrt_device_mu);
      rc = ptpu_pjrt_execute_prog(B->pjrt, B->step_step_prog, args.data(),
                                  int32_t(args.size()), res.data(),
                                  int32_t(res.size()));
    }
    if (rc != 0) {
      // a failed compiled step loses every live hypothesis (the r16
      // backend.error semantics: explicit 500s via slot_failed); the
      // daemon keeps serving
      fprintf(stderr, "decode step execute failed: %s\n",
              ptpu_pjrt_last_error());
      g_metrics.add("paddle_serving_backend_errors_total", 1,
                    "decode ticks lost to a backend failure");
      for (int s = 0; s < S; ++s)
        if (live[s]) {
          admit_failed[size_t(s)] = true;
          (*dead)[s] = true;
        }
      return;
    }
    for (size_t i = 0; i < n_state; ++i) state_buf[i].swap(obufs[i]);
    const int32_t* emit =
        reinterpret_cast<const int32_t*>(obufs[n_state].data());
    const int32_t* done =
        reinterpret_cast<const int32_t*>(obufs[n_state + 1].data());
    for (int s = 0; s < S; ++s) {
      if (!live[s]) continue;
      if (admit_failed[size_t(s)]) {
        (*dead)[s] = true;
        continue;
      }
      (*emitted)[s].push_back(emit[s]);
      emitted_n[s] += 1;
      // natural completion (done), or the request's max_new bound —
      // the slot frees either way (its state stays inert until reuse)
      if (done[s] != 0 || emitted_n[s] >= token_cap[s]) {
        (*dead)[s] = true;
        harvest_final(s);
      }
    }
  }

  // Best-hypothesis id row of the slot's carry state, cut after the
  // first eos — the authoritative /v1/decode answer (streamed tokens
  // are provisional under beam > 1).
  void harvest_final(int s) {
    last_final[size_t(s)].clear();
    if (ids_idx < 0 || scores_idx < 0) return;
    const float* sc = reinterpret_cast<const float*>(
        state_buf[size_t(scores_idx)].data()) + size_t(s) * size_t(beam);
    int best = 0;
    for (int k = 1; k < beam; ++k)
      if (sc[k] > sc[best]) best = k;
    const int32_t* ids = reinterpret_cast<const int32_t*>(
        state_buf[size_t(ids_idx)].data()) +
        (size_t(s) * size_t(beam) + size_t(best)) * size_t(L);
    // the request's max_new bound truncates the answer too (L when
    // the client asked for the full exported max_length)
    int bound = std::min(L, token_cap[size_t(s)] > 0 ? token_cap[size_t(s)]
                                                     : L);
    for (int j = 0; j < bound; ++j) {
      last_final[size_t(s)].push_back(ids[j]);
      if (ids[j] == eos) break;
    }
  }

  bool final_ids(int slot, std::vector<int32_t>* out) override {
    *out = last_final[size_t(slot)];
    return true;
  }

  bool slot_failed(int slot) override {
    return admit_failed[size_t(slot)];
  }
};

// Drain-batch fallback for decode bundles WITHOUT step modules
// (meta.stablehlo_step_skip_reason): each "tick" executes the bundle's
// whole-while_loop module once over the admitted batch and emits every
// token at completion — classic static batching, the pre-r19 serving
// shape. The scheduler forces drain mode (requires_drain).
struct WholeLoopBackend : DecodeBackend {
  std::shared_ptr<const BundleState> B;
  int S = 0;
  int ids_out = -1, mask_out = -1;  // "<gen>" [b,L,1] i32 + its ":mask"
  std::vector<std::vector<Feed>> slot_feeds;
  std::vector<std::vector<int32_t>> last_final;

  std::vector<int> token_cap;      // per-slot max_new bound
  std::vector<bool> fail;          // whole-loop execute failed -> 500

  explicit WholeLoopBackend(std::shared_ptr<const BundleState> b)
      : B(std::move(b)) {
    S = B->sig_static_batch;
    for (size_t i = 0; i < B->sig_outputs.size(); ++i) {
      const std::string& n = B->sig_outputs[i].name;
      for (size_t j = 0; j < B->sig_outputs.size(); ++j)
        if (B->sig_outputs[j].name == n + ":mask" &&
            B->sig_outputs[i].dtype == PTPU_DT_I32) {
          ids_out = int(i);
          mask_out = int(j);
        }
    }
    slot_feeds.assign(size_t(S), {});
    last_final.assign(size_t(S), {});
    token_cap.assign(size_t(S), 0);
    fail.assign(size_t(S), false);
  }

  bool usable() const { return ids_out >= 0 && S > 0; }

  int slots() const override { return S; }
  bool requires_drain() const override { return true; }

  std::string prepare(DecodeReq* r) override {
    return prepare_bundle_feeds(B->sig_inputs, r);
  }

  void admit(int slot, const DecodeReq& r) override {
    slot_feeds[size_t(slot)] = r.feeds;
    token_cap[size_t(slot)] = r.max_new > 0 ? r.max_new : 0;
  }

  void retire(int slot) override {
    slot_feeds[size_t(slot)].clear();
    last_final[size_t(slot)].clear();
    fail[size_t(slot)] = false;
  }

  void tick(const std::vector<bool>& live,
            std::vector<std::vector<int32_t>>* emitted,
            std::vector<bool>* dead) override {
    emitted->assign(size_t(S), {});
    dead->assign(size_t(S), false);
    std::vector<std::vector<uint8_t>> bufs(B->sig_inputs.size());
    std::vector<ptpu_pjrt_tensor> args(B->sig_inputs.size());
    for (size_t i = 0; i < B->sig_inputs.size(); ++i) {
      const SigIO& io = B->sig_inputs[i];
      bufs[i].assign(size_t(sig_elems(io) * sig_isize(io)), 0);
      for (int s = 0; s < S; ++s)
        if (live[s]) fill_feed_row(io, slot_feeds[size_t(s)], &bufs[i], s);
      sig_tensor(&args[i], io, bufs[i].data());
    }
    size_t n_out = B->sig_outputs.size();
    std::vector<std::vector<uint8_t>> obufs(n_out);
    std::vector<ptpu_pjrt_tensor> res(n_out);
    for (size_t i = 0; i < n_out; ++i) {
      const SigIO& io = B->sig_outputs[i];
      obufs[i].assign(size_t(sig_elems(io) * sig_isize(io)), 0);
      sig_tensor(&res[i], io, obufs[i].data());
    }
    int rc;
    {
      std::lock_guard<std::mutex> l(g_pjrt_device_mu);
      rc = ptpu_pjrt_execute_n(B->pjrt, args.data(), int32_t(args.size()),
                               res.data(), int32_t(n_out));
    }
    if (rc != 0) {
      fprintf(stderr, "whole-loop decode failed: %s\n",
              ptpu_pjrt_last_error());
      g_metrics.add("paddle_serving_backend_errors_total", 1,
                    "decode ticks lost to a backend failure");
      for (int s = 0; s < S; ++s)
        if (live[s]) {
          fail[size_t(s)] = true;   // scheduler answers 500
          (*dead)[s] = true;
        }
      return;
    }
    const SigIO& iio = B->sig_outputs[size_t(ids_out)];
    int64_t per = sig_elems(iio) / std::max<int64_t>(iio.dims[0], 1);
    const int32_t* ids =
        reinterpret_cast<const int32_t*>(obufs[size_t(ids_out)].data());
    const float* msk = mask_out >= 0
        ? reinterpret_cast<const float*>(obufs[size_t(mask_out)].data())
        : nullptr;
    const SigIO& mio = B->sig_outputs[size_t(
        mask_out >= 0 ? mask_out : ids_out)];
    int64_t mper = sig_elems(mio) / std::max<int64_t>(mio.dims[0], 1);
    for (int s = 0; s < S; ++s) {
      if (!live[s]) continue;
      last_final[size_t(s)].clear();
      int64_t bound = token_cap[size_t(s)] > 0
                          ? std::min<int64_t>(per, token_cap[size_t(s)])
                          : per;   // the request's max_new bound
      for (int64_t j = 0; j < bound; ++j) {
        if (msk != nullptr && j < mper && msk[s * mper + j] <= 0) break;
        last_final[size_t(s)].push_back(ids[s * per + j]);
      }
      (*emitted)[s] = last_final[size_t(s)];
      (*dead)[s] = true;     // the whole answer arrived: batch done
    }
  }

  bool final_ids(int slot, std::vector<int32_t>* out) override {
    *out = last_final[size_t(slot)];
    return true;
  }

  bool slot_failed(int slot) override { return fail[size_t(slot)]; }
};

// One queued /v1/infer request inside a model's micro-batch gather
// window: parsed typed feeds in, response JSON (or an error + HTTP
// status) out. The handler thread blocks in wait() while the model's
// gather thread coalesces, executes, and scatters.
struct InferJob {
  std::vector<Feed> feeds;
  int64_t rows = 1;        // this request's leading batch dim
  std::string key;         // feed-set shape signature (coalesce guard)
  double deadline = 0;     // absolute now_s() bound (0 = none)
  double t_enq = 0;
  std::string out;         // response body on success
  std::string err;         // error detail otherwise
  int status = 200;
  std::mutex mu;
  std::condition_variable cv;
  bool done = false;

  void finish() {
    std::lock_guard<std::mutex> l(mu);
    done = true;
    cv.notify_all();
  }
  void wait() {
    std::unique_lock<std::mutex> l(mu);
    cv.wait(l, [&] { return done; });
  }
};

struct Daemon {
  int port = 0;
  int listen_fd = -1;
  int threads = 16;
  std::string backend = "auto";   // auto | interp | pjrt | toy
  bool drain_batch = false;
  int slots = 8;
  int toy_hidden = 64;
  int toy_vocab = 1000;
  int toy_tick_us = 0;
  int max_new_cap = 64;
  size_t max_queue = 256;
  size_t queue_high_water = 0;    // load-shed bound (0 = 3/4 max_queue)
  double default_deadline_ms = 0; // per-request bound when the client
                                  // sends none (0 = no deadline)
  double drain_timeout_s = 30;    // graceful SIGTERM drain budget
  double tick_hang_ms = 5000;     // watchdog stall bound (0 = off)
  size_t max_body_bytes = 16u << 20;  // request body cap -> 413
  int io_timeout_ms = 30000;      // slow-client read/write bound -> 408
  std::string pjrt_plugin, pjrt_options, pjrt_platform = "tpu";
  double batch_window_ms = 0;     // /v1/infer gather window (0 = off:
                                  // the classic per-request path)
  int batch_max = 64;             // max coalesced rows per execute
                                  // (pjrt clamps to its largest rung)
  size_t batch_max_queue = 256;   // per-model gather queue bound -> 503
  size_t host_cache_rows = 65536; // per host table: LRU row-cache bound
                                  // (rows, not bytes) — the resident
                                  // footprint knob for mmap-backed
                                  // host-resident tables
  int infer_exec_us = 0;          // toy SERIALIZED per-execute cost —
                                  // the infer twin of --toy_tick_us:
                                  // one device, one dispatch queue, a
                                  // fixed price per execute regardless
                                  // of gathered rows
  std::mutex exec_dev_mu;

  // One served model: its live bundle pointer (swapped atomically by
  // an isolated per-model reload) and, when --batch_window_ms > 0,
  // its own infer gather queue + thread — one model's torn publish or
  // stalled window never touches a neighbor's.
  struct ModelState {
    std::string name;
    std::string path;                           // guarded by mu
    std::shared_ptr<const BundleState> bundle;  // guarded by mu
    std::mutex mu;              // guards path + bundle pointer swaps
    std::mutex reload_mu;       // serializes reload attempts
    std::deque<std::shared_ptr<InferJob>> q;    // guarded by qmu
    std::mutex qmu;
    std::condition_variable qcv;
    std::thread gather;
  };
  // --bundle model=path specs in flag order; the first is the default
  // model (bare --bundle path keeps the single-model behavior under
  // the name "default"). The map itself is built before any thread
  // starts and never mutated after — only per-model state moves.
  std::vector<std::pair<std::string, std::string>> bundle_specs;
  std::vector<std::string> model_order;
  std::map<std::string, std::shared_ptr<ModelState>> models;
  std::string default_model = "default";
  bool bundle_decode = false;     // a bundle decode backend holds the
                                  // DEFAULT model's compiled step
                                  // programs: hot-swap would pull them
                                  // out from under live slots — that
                                  // model's reload is refused (409)

  Scheduler sched;
  std::atomic<bool> stop{false};
  std::atomic<bool> ready{false};     // /readyz: false while draining
  std::atomic<bool> tick_live{true};  // /healthz: false on watchdog stall
  std::atomic<bool> draining{false};
  std::atomic<int> active_work{0};    // in-flight infer/decode/reload
  std::atomic<int64_t> tick_busy_since_us{0};
  std::thread watchdog;
  int stop_pipe[2] = {-1, -1};    // wakes the accept loop out of poll
  std::vector<std::thread> workers;
  std::mutex conn_mu;
  std::condition_variable conn_cv;
  std::deque<int> conns;

  // "" resolves to the default model (single-bundle daemons keep the
  // pre-multi-model behavior untouched); unknown names return null —
  // the caller answers 404.
  ModelState* model_state(const std::string& name) {
    auto it = models.find(name.empty() ? default_model : name);
    return it == models.end() ? nullptr : it->second.get();
  }

  std::shared_ptr<const BundleState> cur_bundle(
      const std::string& model = "") {
    ModelState* m = model_state(model);
    if (m == nullptr) return nullptr;
    std::lock_guard<std::mutex> l(m->mu);
    return m->bundle;
  }

  // a model's path is written by its successful reload while handler
  // threads read it (the /v1/reload default target, SIGHUP) — both
  // sides go through that model's mu
  std::string cur_bundle_path(const std::string& model = "") {
    ModelState* m = model_state(model);
    if (m == nullptr) return "";
    std::lock_guard<std::mutex> l(m->mu);
    return m->path;
  }

  // Load `path` into a fresh immutable BundleState. `is_reload` counts
  // the reload.torn fault point and never mutates daemon state — the
  // caller validates + swaps. On the initial load, resolves
  // backend=="auto" to "interp" (mutating this->backend) exactly as
  // before.
  std::shared_ptr<BundleState> load_bundle_state(const std::string& path,
                                                 bool is_reload,
                                                 std::string* err) {
    auto st = std::make_shared<BundleState>();
    std::string json, tar;
    std::string e = ptpu::read_bundle(path.c_str(), &json, &tar);
    if (!e.empty()) { *err = e; return nullptr; }
    bool torn_injected = false;
    if (is_reload && g_faults.fire("reload.torn") != nullptr) {
      // the new bundle's bytes arrived truncated mid-tar: integrity
      // validation below must catch it and leave the old version live
      tar.resize(tar.size() / 2);
      torn_injected = true;
    }
    JParser jp{json.data(), json.data() + json.size()};
    JValue cfg = jp.parse();
    if (!jp.ok) { *err = "bad bundle JSON"; return nullptr; }
    if (const JValue* meta = cfg.get("meta")) {
      if (const JValue* v = meta->get("bundle_version"))
        st->version = v->num;
      if (const JValue* c = meta->get("param_crc32")) st->crc = c->str;
      // quantization signature: FAIL CLOSED on anything unknown. A
      // param dtype this build does not understand must refuse at load
      // (initial load -> startup error, reload -> 409) — silently
      // reinterpreting the bytes would serve garbage with a 200.
      if (const JValue* q = meta->get("quantize")) {
        if (const JValue* m = q->get("mode")) st->quant_mode = m->str;
        if (st->quant_mode != "bf16" && st->quant_mode != "int8") {
          *err = "unsupported quantize mode '" + st->quant_mode +
                 "' in bundle meta — refusing to load (this build "
                 "serves bf16 and int8 quantized bundles)";
          return nullptr;
        }
        if (const JValue* pd = q->get("param_dtypes"))
          for (const auto& [pname, tv] : pd->obj)
            if (!ptpu::known_param_dtype(tv.str)) {
              *err = "unsupported param dtype '" + tv.str +
                     "' for parameter '" + pname + "' in the bundle "
                     "signature — refusing to load rather than "
                     "reinterpret bytes (known: f32, bf16, int8)";
              return nullptr;
            }
        st->quantize_json = json_emit(*q);
      }
      if (const JValue* pb = meta->get("param_bytes")) {
        st->param_bytes_json = json_emit(*pb);
        if (const JValue* t = pb->get("total"))
          st->param_bytes_total = t->num;
        if (const JValue* by = pb->get("by_dtype"))
          for (const auto& [k, v] : by->obj)
            st->param_bytes_by_dtype.push_back({k, v.num});
      }
    }
    if (!st->crc.empty()) {
      char got[16];
      snprintf(got, sizeof(got), "%08x",
               ptpu::crc32(reinterpret_cast<const uint8_t*>(tar.data()),
                           tar.size()));
      if (st->crc != got) {
        *err = "bundle parameter crc mismatch (torn write?): meta says " +
               st->crc + ", tar bytes hash to " + got;
        return nullptr;
      }
    } else if (torn_injected) {
      *err = "torn bundle read (injected) and bundle carries no "
             "param_crc32 to catch it";
      return nullptr;
    }
    if (const JValue* layers = cfg.get("layers"))
      for (const auto& jl : layers->arr) {
        if (jl.get("type")->str != "data") continue;
        FeedDef fd;
        fd.name = jl.get("name")->str;
        if (const JValue* c = jl.get("cfg"))
          if (const JValue* it = c->get("input_type")) {
            if (const JValue* k = it->get("kind")) fd.kind = k->str;
            if (const JValue* sq = it->get("seq_type"))
              fd.is_seq = sq->num != 0;
          }
        if (fd.kind.empty()) fd.kind = "dense";
        st->feed_defs.push_back(fd);
      }
    if (const JValue* outs = cfg.get("outputs"))
      for (const auto& o : outs->arr) st->output_names.push_back(o.str);
    if (const JValue* meta = cfg.get("meta"))
      if (const JValue* ht = meta->get("host_tables")) {
        // host-resident tables: mmap the sidecar rows in place. The
        // offsets come from the SAME in-memory tar the crc above
        // validated; the mmap re-opens `path`, and the sidecar's own
        // header/id crcs (validated here) catch a file swapped by a
        // racing publish between the read and the map.
        st->host_tables_json = json_emit(*ht);
        auto tindex = ptpu::tar_index(tar);
        size_t tar_off = 16 + json.size();
        for (const auto& [tname, tv] : ht->obj) {
          auto hs = std::make_shared<HostRowStore>();
          hs->table = tname;
          if (const JValue* x = tv.get("vocab")) hs->vocab = int64_t(x->num);
          if (const JValue* x = tv.get("width")) hs->width = int64_t(x->num);
          if (const JValue* x = tv.get("block_rows"))
            hs->block_rows = int64_t(x->num);
          if (const JValue* x = tv.get("dense")) hs->dense_src = x->b;
          if (const JValue* x = tv.get("entry")) hs->entry = x->str;
          if (const JValue* x = tv.get("feeds"))
            for (const auto& fn : x->arr) hs->feeds.push_back(fn.str);
          if (const JValue* x = tv.get("dtype"))
            if (x->str != "f32") {
              // fail closed — never reinterpret row bytes
              *err = "host table '" + tname + "': unsupported row dtype '" +
                     x->str + "' (this build stages f32 rows)";
              return nullptr;
            }
          if (hs->width <= 0 || hs->vocab < 0 || hs->block_rows <= 0) {
            *err = "host table '" + tname +
                   "': malformed meta.host_tables record";
            return nullptr;
          }
          auto ent = tindex.find(hs->entry);
          if (ent == tindex.end()) {
            *err = "host table '" + tname + "': rows sidecar entry '" +
                   hs->entry + "' is missing from the parameter tar";
            return nullptr;
          }
          hs->cache_cap = host_cache_rows;
          std::string e2 = hs->open_map(path, tar_off + ent->second.first,
                                        ent->second.second);
          if (!e2.empty()) { *err = e2; return nullptr; }
          if (!is_reload)
            fprintf(stderr,
                    "host table '%s': vocab=%lld width=%lld sidecar "
                    "rows=%lld (%s), LRU bound --host_cache_rows=%zu\n",
                    tname.c_str(), (long long)hs->vocab,
                    (long long)hs->width, (long long)hs->n_rows,
                    hs->contiguous ? "dense prefix" : "sparse ids",
                    hs->cache_cap);
          st->host_stores[tname] = hs;
        }
      }
    if (const JValue* meta = cfg.get("meta")) {
      // decode metadata, any build: generation bundles expose
      // ':ids'/':scores' outputs; a missing step export records why
      if (const JValue* skip = meta->get("stablehlo_step_skip_reason"))
        st->step_skip_reason = skip->str;
      if (const JValue* sh0 = meta->get("stablehlo"))
        if (const JValue* sig0 = sh0->get("signature"))
          if (const JValue* outs0 = sig0->get("outputs"))
            for (const auto& o : outs0->arr)
              if (const JValue* n = o.get("name"))
                if (n->str.size() > 4 &&
                    n->str.compare(n->str.size() - 4, 4, ":ids") == 0)
                  st->has_decode = true;
      if (const JValue* sh = meta->get("stablehlo")) {
        if (const JValue* sig = sh->get("signature")) {
          // the served signature JSON carries the step sub-signature
          // beside the forward one, so /v1/signature answers "can this
          // replica stream-decode" without a second endpoint
          JValue merged = *sig;
          if (const JValue* stp = meta->get("stablehlo_step"))
            if (const JValue* ssig = stp->get("signature"))
              merged.obj["step"] = *ssig;
          // the quantization record + byte accounting ride the served
          // signature: "what precision and how many bytes is this
          // replica serving" is a /v1/signature fact
          if (const JValue* q = meta->get("quantize"))
            merged.obj["quantize"] = *q;
          if (const JValue* pb = meta->get("param_bytes"))
            merged.obj["param_bytes"] = *pb;
          // host-backed tables ride the served signature: "which ids
          // stage through the row store" is a /v1/signature fact
          if (const JValue* ht2 = meta->get("host_tables"))
            merged.obj["host_tables"] = *ht2;
          st->signature_json = json_emit(merged);
        }
        // dims reader: 'b' (the symbolic batch) resolves to `batch`;
        // inputs tagged role "host_rows" are remembered — their leading
        // dim is the staged-row budget R, which pjrt_execute must never
        // scale with the exec batch
        auto rd = [&st](const JValue* arr, std::vector<SigIO>* out,
                        int64_t batch) {
          if (!arr) return;
          for (const auto& e2 : arr->arr) {
            SigIO io;
            io.name = e2.get("name")->str;
            if (const JValue* role = e2.get("role"))
              if (role->str == "host_rows" && out == &st->sig_inputs)
                st->host_row_inputs.insert(io.name);
            std::string dt = e2.get("dtype")->str;
            io.dtype = dt == "i32" ? PTPU_DT_I32
                       : dt == "i64" ? PTPU_DT_I64
                       : dt == "pred" ? PTPU_DT_PRED
                       : PTPU_DT_F32;
            if (const JValue* sh2 = e2.get("shape"))
              for (const auto& d : sh2->arr)
                io.dims.push_back(d.kind == JValue::kStr ? batch
                                                         : int64_t(d.num));
            out->push_back(io);
          }
        };
        if (const JValue* sig = sh->get("signature")) {
          if (const JValue* sb = sig->get("static_batch"))
            st->sig_static_batch = int(sb->num);
          rd(sig->get("inputs"), &st->sig_inputs, st->sig_static_batch);
          rd(sig->get("outputs"), &st->sig_outputs, st->sig_static_batch);
        }
        if (backend == "pjrt") {
          std::string key = "mlir_" + pjrt_platform + "_b64";
          const JValue* m = sh->get(key);
          if (m == nullptr) {
            *err = "bundle has no " + key + " module";
            return nullptr;
          }
          std::string code;
          if (!ptpu::b64_decode(m->str, &code)) {
            *err = "bad base64 in " + key;
            return nullptr;
          }
          {
            // a reload compiles the new module while the old runner
            // still serves — creation must not race an execute. Whether
            // a plugin allows a second client on a device the live
            // client holds is plugin-dependent: libtpu 0.0.34 on a v5e
            // did (tried once by hand at PR 21: /v1/reload of an infer
            // bundle answered ok in 3.8 s and the next /v1/infer was
            // right); it is not part of chip_smoke.py.
            std::lock_guard<std::mutex> l(g_pjrt_device_mu);
            st->pjrt = ptpu_pjrt_create_opts(
                pjrt_plugin.c_str(), code.data(), int64_t(code.size()),
                pjrt_options.empty() ? nullptr : pjrt_options.c_str());
          }
          if (st->pjrt == nullptr) {
            *err = std::string("pjrt backend: ") + ptpu_pjrt_last_error();
            return nullptr;
          }
          // batch-ladder modules (mlir_<platform>_b<N>_b64, rungs
          // listed by signature.batch_ladder): compiled as additional
          // programs on the same runner via the multi-program ABI. A
          // rung that fails to decode or compile is skipped — the
          // static-batch module still serves, the batcher just loses
          // that bucket shape.
          if (const JValue* sig = sh->get("signature"))
            if (const JValue* lad = sig->get("batch_ladder"))
              for (const auto& r2 : lad->arr) {
                int rung = int(r2.num);
                const JValue* lm = sh->get(
                    "mlir_" + pjrt_platform + "_b" +
                    std::to_string(rung) + "_b64");
                std::string lcode;
                if (rung <= 0 || lm == nullptr ||
                    !ptpu::b64_decode(lm->str, &lcode))
                  continue;
                std::lock_guard<std::mutex> l(g_pjrt_device_mu);
                int prog = ptpu_pjrt_add_program(
                    st->pjrt, lcode.data(), int64_t(lcode.size()));
                if (prog >= 0) st->ladder.push_back({rung, prog});
                else
                  fprintf(stderr,
                          "batch ladder rung %d compile failed: %s\n",
                          rung, ptpu_pjrt_last_error());
              }
          std::sort(st->ladder.begin(), st->ladder.end());
          // per-tick decode step modules (meta.stablehlo_step):
          // compiled as additional programs on the SAME runner/client,
          // so continuous decode shares the device with /v1/infer
          if (const JValue* stp = meta->get("stablehlo_step")) {
            const JValue* ssig = stp->get("signature");
            std::string ik = "init_mlir_" + pjrt_platform + "_b64";
            std::string sk = "step_mlir_" + pjrt_platform + "_b64";
            const JValue* im = stp->get(ik);
            const JValue* sm = stp->get(sk);
            std::string icode, scode;
            if (ssig != nullptr && im != nullptr && sm != nullptr &&
                ptpu::b64_decode(im->str, &icode) &&
                ptpu::b64_decode(sm->str, &scode)) {
              if (const JValue* v = ssig->get("slots"))
                st->step_slots = int(v->num);
              if (const JValue* v = ssig->get("beam"))
                st->step_beam = int(v->num);
              if (const JValue* v = ssig->get("max_length"))
                st->step_max_len = int(v->num);
              if (const JValue* v = ssig->get("eos_id"))
                st->step_eos = int(v->num);
              rd(ssig->get("inputs"), &st->step_inputs, st->step_slots);
              rd(ssig->get("state"), &st->step_state, st->step_slots);
              rd(ssig->get("enc"), &st->step_enc, st->step_slots);
              std::lock_guard<std::mutex> l(g_pjrt_device_mu);
              st->step_init_prog = ptpu_pjrt_add_program(
                  st->pjrt, icode.data(), int64_t(icode.size()));
              st->step_step_prog = ptpu_pjrt_add_program(
                  st->pjrt, scode.data(), int64_t(scode.size()));
              if (st->step_init_prog < 0 || st->step_step_prog < 0) {
                // compilation failure degrades to drain-batch decode
                // with the reason logged, never a dead daemon
                st->step_skip_reason =
                    std::string("step module compile failed: ") +
                    ptpu_pjrt_last_error();
                st->step_init_prog = st->step_step_prog = -1;
              }
            } else if (st->step_skip_reason.empty()) {
              st->step_skip_reason =
                  "bundle's stablehlo_step lacks a " + pjrt_platform +
                  " module or a signature";
            }
          }
        }
      } else if (const JValue* skip = meta->get("stablehlo_skip_reason")) {
        st->signature_json =
            "{\"skip_reason\":\"" + ptpu::json_escape(skip->str) + "\"";
        if (!st->quantize_json.empty())
          st->signature_json += ",\"quantize\":" + st->quantize_json;
        if (!st->param_bytes_json.empty())
          st->signature_json += ",\"param_bytes\":" + st->param_bytes_json;
        if (!st->host_tables_json.empty())
          st->signature_json += ",\"host_tables\":" + st->host_tables_json;
        st->signature_json += "}";
        if (backend == "pjrt") {
          *err = "bundle has no StableHLO export: " + skip->str;
          return nullptr;
        }
      }
    }
    if (!is_reload && st->has_decode && !st->step_skip_reason.empty())
      // never a silent whole-loop-only bundle: the operator can read
      // WHY this decode serves drain-batch instead of continuous
      fprintf(stderr,
              "decode step modules absent (%s) — decode serves "
              "drain-batch over the whole-loop module (pjrt backend "
              "only)\n",
              st->step_skip_reason.c_str());
    std::string want = backend;
    if (want == "auto" || want == "interp") {
      // the engine consumes the SAME bytes the crc/signature checks
      // above validated — a path re-read would race a concurrent
      // publish to the same file (the SIGHUP pattern) and could load
      // torn content the validation never saw
      st->engine = ptpu_engine_create_from_parts(
          json.data(), int64_t(json.size()), tar.data(),
          int64_t(tar.size()));
      if (st->engine == nullptr) {
        if (want == "interp") {
          *err = std::string("interp backend: ") + ptpu_engine_last_error();
          return nullptr;
        }
      } else if (want == "auto") {
        want = "interp";
      }
    }
    if (want == "auto") {
      *err = std::string("no backend can serve this bundle (interp: ") +
             ptpu_engine_last_error() + "); use --backend pjrt with a "
             "plugin, or serve through the embedded-Python capi";
      return nullptr;
    }
    if (backend != want) backend = want;  // initial-load auto resolution
    return st;
  }

  // paddle_serving_param_bytes{dtype}: the live bundle's parameter
  // payload bytes by storage dtype (quant.py tags). The canonical tags
  // are always (re)set — a reload from int8 back to f32 must zero the
  // int8 series, not leave it stale.
  static void set_param_bytes_gauges(const BundleState& st) {
    static const char* kHelp =
        "live bundle parameter payload bytes by storage dtype";
    static const char* kTags[] = {"f32", "bf16", "int8"};
    for (const char* t : kTags) {
      double v = 0;
      for (const auto& [k, b] : st.param_bytes_by_dtype)
        if (k == t) v = b;
      g_metrics.set("paddle_serving_param_bytes", v, kHelp,
                    std::string("dtype=\"") + t + "\"");
    }
    for (const auto& [k, b] : st.param_bytes_by_dtype) {
      bool canon = false;
      for (const char* t : kTags) canon = canon || k == t;
      if (!canon)
        g_metrics.set("paddle_serving_param_bytes", b, kHelp,
                      "dtype=\"" + k + "\"");
    }
    g_metrics.set("paddle_serving_param_bytes_total", st.param_bytes_total,
                  "live bundle total parameter payload bytes");
  }

  // Per-model publication of the live bundle's gauges: the unlabeled
  // series keep their exact pre-multi-model meaning (they track the
  // DEFAULT model, so existing dashboards/probes read on unchanged)
  // and every model — default included — gets a model="..." twin.
  void publish_bundle_metrics(const std::string& model,
                              const BundleState& st) {
    static const char* kVerHelp =
        "bundle_version of the live parameter bundle";
    if (model == default_model) {
      g_metrics.set("paddle_serving_param_version", st.version, kVerHelp);
      set_param_bytes_gauges(st);
    }
    g_metrics.set("paddle_serving_param_version", st.version, kVerHelp,
                  "model=\"" + model + "\"");
  }

  bool load_bundle(std::string* err) {
    for (const auto& [mname, mpath] : bundle_specs) {
      if (models.count(mname) != 0) {
        *err = "duplicate --bundle model name '" + mname + "'";
        return false;
      }
      auto st = load_bundle_state(mpath, /*is_reload=*/false, err);
      if (st == nullptr) {
        *err = "model '" + mname + "': " + *err;
        return false;
      }
      auto ms = std::make_shared<ModelState>();
      ms->name = mname;
      ms->path = mpath;
      ms->bundle = st;
      models[mname] = ms;
      model_order.push_back(mname);
    }
    default_model = model_order.front();
    for (const auto& mname : model_order)
      publish_bundle_metrics(mname, *models[mname]->bundle);
    g_metrics.set("paddle_serving_models", double(models.size()),
                  "models served by this daemon (--bundle count)");
    return true;
  }

  // POST /v1/reload + SIGHUP: load `path` into a second immutable
  // engine, validate it against the named model's live bundle,
  // pointer-flip. Returns the HTTP status; *msg is the response detail
  // either way. The old engine keeps serving every request that
  // snapshotted it and frees when the last one releases the
  // shared_ptr. Reloads are ISOLATED per model: each model has its own
  // reload_mu and version/crc lineage, so model A's torn publish 409s
  // while model B's requests (and reloads) flow untouched.
  int do_reload(const std::string& model, const std::string& path,
                std::string* msg) {
    ModelState* ms = model_state(model);
    if (ms == nullptr) {
      if (models.empty()) {
        *msg = "no bundle to reload (toy/decode-only daemon)";
        return 400;
      }
      *msg = "unknown model '" + model + "'";
      return 404;
    }
    std::lock_guard<std::mutex> rl(ms->reload_mu);
    auto live = cur_bundle(ms->name);
    if (live == nullptr) {
      *msg = "no bundle to reload (toy/decode-only daemon)";
      return 400;
    }
    if (bundle_decode && ms->name == default_model) {
      // the decode scheduler executes the live bundle's compiled step
      // programs with per-slot carry state derived from THOSE
      // parameters; a mid-decode parameter swap would silently mix
      // models inside a slot. Restart to swap decode parameters.
      *msg = "bundle hot-swap is not supported while a bundle decode "
             "backend is active (per-slot carry state pins the live "
             "parameters); restart the daemon to swap";
      return 409;
    }
    auto reject = [&](const std::string& why, int code) {
      g_metrics.add("paddle_serving_reloads_total", 1,
                    "parameter hot-swap attempts",
                    "result=\"rejected\"");
      g_metrics.add("paddle_serving_reloads_total", 1,
                    "parameter hot-swap attempts",
                    "model=\"" + ms->name + "\",result=\"rejected\"");
      *msg = why;
      return code;
    };
    std::string err;
    auto st = load_bundle_state(path, /*is_reload=*/true, &err);
    if (st == nullptr) return reject(err, 409);
    // the swap must be invisible to clients: identical feed surface
    // and output set, or the new bundle is a different model — reject
    if (!(st->feed_defs == live->feed_defs))
      return reject("bundle signature mismatch: feed set differs from "
                    "the live bundle", 409);
    if (st->output_names != live->output_names)
      return reject("bundle signature mismatch: output set differs from "
                    "the live bundle", 409);
    // paddle_serving_param_version is MONOTONE: a regressing version is
    // a stale bundle (a delayed publish racing a newer one, or operator
    // error) — serving it would silently un-train the model. Rollbacks
    // re-stamp known-good parameters under a FRESH version instead
    // (serving_publisher.py). Re-reading the SAME version is the
    // documented SIGHUP/empty-body form, but only for identical bytes:
    // an equal version with a different parameter crc is a collision
    // two writers must never have produced.
    if (st->version < live->version) {
      char vbuf[160];
      snprintf(vbuf, sizeof(vbuf),
               "bundle_version regressed: live serves %.0f, candidate is "
               "%.0f — republish under a fresh version",
               live->version, st->version);
      return reject(vbuf, 409);
    }
    if (st->version == live->version && !st->crc.empty() &&
        !live->crc.empty() && st->crc != live->crc)
      return reject("bundle_version collision: candidate carries the live "
                    "version " + std::to_string(int64_t(live->version)) +
                    " but different parameter bytes (crc " + st->crc +
                    " vs live " + live->crc + ")", 409);
    {
      std::lock_guard<std::mutex> l(ms->mu);
      ms->bundle = st;
      ms->path = path;
    }
    g_metrics.add("paddle_serving_reloads_total", 1,
                  "parameter hot-swap attempts", "result=\"ok\"");
    g_metrics.add("paddle_serving_reloads_total", 1,
                  "parameter hot-swap attempts",
                  "model=\"" + ms->name + "\",result=\"ok\"");
    publish_bundle_metrics(ms->name, *st);
    char buf[160];
    snprintf(buf, sizeof(buf),
             "{\"result\":\"ok\",\"version\":%.0f,\"param_crc32\":\"%s\"}",
             st->version, st->crc.c_str());
    *msg = buf;
    return 200;
  }

  // ---- HTTP plumbing ----

  bool start_listen(std::string* err) {
    listen_fd = socket(AF_INET, SOCK_STREAM, 0);
    if (listen_fd < 0) { *err = "socket() failed"; return false; }
    int one = 1;
    setsockopt(listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr;
    memset(&addr, 0, sizeof(addr));
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    addr.sin_port = htons(uint16_t(port));
    if (bind(listen_fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
      *err = "bind failed (port in use?)";
      return false;
    }
    socklen_t alen = sizeof(addr);
    getsockname(listen_fd, (sockaddr*)&addr, &alen);
    port = ntohs(addr.sin_port);
    if (listen(listen_fd, 128) != 0) { *err = "listen failed"; return false; }
    return true;
  }

  // The accept loop: polls the listen socket against an internal stop
  // pipe, so the daemon can stop accepting without signals racing
  // accept(2). Run on its own thread; workers are started separately
  // (start_http) so the drain sequence can stop them in order.
  void serve() {
    pollfd fds[2];
    fds[0].fd = listen_fd;
    fds[0].events = POLLIN;
    fds[1].fd = stop_pipe[0];
    fds[1].events = POLLIN;
    while (true) {
      fds[0].revents = fds[1].revents = 0;
      int rc = poll(fds, 2, -1);
      if (rc < 0) {
        if (errno == EINTR) continue;
        break;
      }
      if (fds[1].revents != 0) break;  // ordered-shutdown wakeup
      if (fds[0].revents == 0) continue;
      int fd = accept(listen_fd, nullptr, nullptr);
      if (fd < 0) { if (stop) break; continue; }
      {
        std::lock_guard<std::mutex> l(conn_mu);
        conns.push_back(fd);
      }
      conn_cv.notify_one();
    }
  }

  // False on resource exhaustion (no stop pipe = no way to ever wake
  // the accept loop for shutdown — refuse to start instead).
  bool start_http() {
    if (pipe(stop_pipe) != 0) {
      stop_pipe[0] = stop_pipe[1] = -1;
      return false;
    }
    for (int i = 0; i < threads; ++i)
      workers.emplace_back([this] { worker(); });
    if (batch_window_ms > 0)
      for (auto& [mname, ms] : models)
        ms->gather = std::thread([this, m = ms.get()] { batcher_loop(m); });
    if (sched.backend && tick_hang_ms > 0) {
      sched.tick_busy_us = &tick_busy_since_us;
      watchdog = std::thread([this] { watchdog_loop(); });
    }
    ready = true;
    g_metrics.set("paddle_serving_ready", 1,
                  "1 while accepting new work (0 once draining)");
    return true;
  }

  // The watchdog: a scheduler tick that exceeds --tick_hang_ms fails
  // liveness (/healthz -> 503) instead of wedging the slot scheduler
  // silently. Liveness recovers if the tick eventually completes; the
  // stall is counted either way.
  void watchdog_loop() {
    bool stalled_prev = false;
    const int64_t bound_us = int64_t(tick_hang_ms * 1000);
    const int64_t nap_us =
        std::max<int64_t>(1000, std::min<int64_t>(bound_us / 4, 50000));
    while (!stop) {
      int64_t t0 = tick_busy_since_us.load();
      bool stalled = t0 != 0 && now_us() - t0 > bound_us;
      tick_live = !stalled;
      if (stalled && !stalled_prev)
        g_metrics.add("paddle_serving_watchdog_stall_total", 1,
                      "decode ticks caught exceeding --tick_hang_ms");
      stalled_prev = stalled;
      std::this_thread::sleep_for(std::chrono::microseconds(nap_us));
    }
  }

  void worker() {
    while (true) {
      int fd = -1;
      {
        std::unique_lock<std::mutex> l(conn_mu);
        conn_cv.wait(l, [&] { return stop || !conns.empty(); });
        if (stop && conns.empty()) return;
        fd = conns.front();
        conns.pop_front();
      }
      // a wedged client must not pin this session thread forever:
      // recv/send time out (-> 408) after --io_timeout_ms
      timeval tv{io_timeout_ms / 1000, (io_timeout_ms % 1000) * 1000};
      setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
      setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
      // HTTP/1.1 keep-alive: serve requests on this connection until
      // the client closes, asks for Connection: close, errors, or the
      // daemon stops (streaming clients hold one connection and see
      // tokens as ticks emit them — connection-per-request is gone).
      // `carry` holds bytes received past one request's body — a
      // pipelining client's next request must not be dropped.
      std::string carry;
      bool first = true;
      while (!stop) {
        if (!handle(fd, first, &carry)) break;
        first = false;
      }
      close(fd);
    }
  }

  // Returns 0 on a complete request, an HTTP status the caller should
  // answer with (408 slow client, 413 body too large), or -1 for a
  // closed/garbled/idle connection not worth a response. *deadline_ms
  // picks up the X-Deadline-Ms header (0 when absent); *want_close is
  // set when the client asked for Connection: close (or HTTP/1.0).
  // *carry holds surplus bytes received past this request's body (a
  // pipelining client's next request) — consumed first on the next
  // call. Idle keep-alive waits poll in short slices so a stop/drain
  // never blocks on a silent connection; a kept-alive connection that
  // has already been served (`!first`) also yields — quiet close —
  // the moment OTHER connections are queued for a worker, so `threads`
  // idle keep-alive clients cannot starve the pool (or /healthz).
  int read_request(int fd, std::string* method, std::string* path,
                   std::string* body, double* deadline_ms,
                   std::string* model_hdr, bool* want_close,
                   std::string* carry, bool first) {
    *deadline_ms = 0;
    model_hdr->clear();
    *want_close = false;
    if (carry->empty()) {
      double idle_deadline = now_s() + io_timeout_ms / 1000.0;
      pollfd p;
      p.fd = fd;
      p.events = POLLIN;
      for (;;) {
        // stop: close idle connections so worker joins stay bounded
        // (draining still answers — new work gets its explicit 503)
        if (stop) return -1;
        p.revents = 0;
        int rc = poll(&p, 1, 250);
        if (rc > 0) break;
        if (rc < 0 && errno != EINTR) return -1;
        if (now_s() >= idle_deadline) return -1;   // idle: quiet close
        if (!first) {
          std::lock_guard<std::mutex> l(conn_mu);
          if (!conns.empty()) return -1;  // yield to waiting clients
        }
      }
    }
    std::string buf;
    buf.swap(*carry);
    char tmp[4096];
    size_t hdr_end = buf.find("\r\n\r\n");   // carried bytes may already
                                             // hold a full header
    while (hdr_end == std::string::npos) {
      ssize_t n = recv(fd, tmp, sizeof(tmp), 0);
      if (n < 0 && errno == EINTR) continue;  // signal, not the client
      if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK))
        return buf.empty() ? -1 : 408;  // half-sent stall: 408; idle: close
      if (n <= 0) return -1;
      buf.append(tmp, size_t(n));
      hdr_end = buf.find("\r\n\r\n");
      if (buf.size() > (1u << 20) && hdr_end == std::string::npos)
        return -1;
    }
    std::string head = buf.substr(0, hdr_end);
    size_t sp1 = head.find(' ');
    size_t sp2 = head.find(' ', sp1 + 1);
    if (sp1 == std::string::npos || sp2 == std::string::npos) return -1;
    *method = head.substr(0, sp1);
    *path = head.substr(sp1 + 1, sp2 - sp1 - 1);
    size_t clen = 0;
    {
      // case-insensitive header scans
      std::string lower = head;
      std::transform(lower.begin(), lower.end(), lower.begin(), ::tolower);
      size_t p = lower.find("content-length:");
      if (p != std::string::npos)
        clen = size_t(strtoll(head.c_str() + p + 15, nullptr, 10));
      p = lower.find("x-deadline-ms:");
      if (p != std::string::npos)
        *deadline_ms = strtod(head.c_str() + p + 14, nullptr);
      p = lower.find("x-model:");
      if (p != std::string::npos) {
        // value read from `head` (model names are case-sensitive);
        // only the header NAME scan is case-folded
        size_t e = head.find('\n', p);
        std::string mv = head.substr(
            p + 8, (e == std::string::npos ? head.size() : e) - p - 8);
        size_t b0 = mv.find_first_not_of(" \t");
        size_t b1 = mv.find_last_not_of(" \t\r");
        if (b0 != std::string::npos) *model_hdr = mv.substr(b0, b1 - b0 + 1);
      }
      p = lower.find("connection:");
      if (p != std::string::npos) {
        size_t e = lower.find('\n', p);
        if (lower.substr(p, e - p).find("close") != std::string::npos)
          *want_close = true;
      }
      if (lower.find("http/1.0") != std::string::npos) *want_close = true;
    }
    if (clen > max_body_bytes) return 413;   // the body bound: clen is
                                             // authoritative (the read
                                             // loop below stops at it)
    *body = buf.substr(hdr_end + 4);
    while (body->size() < clen) {
      ssize_t n = recv(fd, tmp, sizeof(tmp), 0);
      if (n < 0 && errno == EINTR) continue;
      if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) return 408;
      if (n <= 0) return -1;
      body->append(tmp, size_t(n));
    }
    // bytes past the body belong to the NEXT pipelined request —
    // hand them back instead of truncating them away
    if (body->size() > clen) {
      carry->assign(*body, clen, std::string::npos);
      body->resize(clen);
    }
    return 0;
  }

  static void respond(int fd, int code, const std::string& body,
                      const char* ctype = "application/json",
                      const char* extra_headers = "", bool keep = false) {
    const char* msg = code == 200   ? "OK"
                      : code == 404 ? "Not Found"
                      : code == 408 ? "Request Timeout"
                      : code == 409 ? "Conflict"
                      : code == 413 ? "Payload Too Large"
                      : code == 500 ? "Internal Server Error"
                      : code == 503 ? "Service Unavailable"
                      : code == 504 ? "Gateway Timeout"
                                    : "Bad Request";
    std::ostringstream o;
    o << "HTTP/1.1 " << code << ' ' << msg << "\r\nContent-Type: " << ctype
      << "\r\nContent-Length: " << body.size()
      << "\r\n" << extra_headers << "Connection: "
      << (keep ? "keep-alive" : "close") << "\r\n\r\n" << body;
    std::string s = o.str();
    size_t off = 0;
    while (off < s.size()) {
      ssize_t n = send(fd, s.data() + off, s.size() - off, MSG_NOSIGNAL);
      if (n <= 0) return;
      off += size_t(n);
    }
  }

  // ---- chunked token streaming (POST /v1/decode {"stream": true}) ----

  static bool send_all(int fd, const std::string& s) {
    size_t off = 0;
    while (off < s.size()) {
      ssize_t n = send(fd, s.data() + off, s.size() - off, MSG_NOSIGNAL);
      if (n <= 0) return false;
      off += size_t(n);
    }
    return true;
  }

  static bool send_chunk(int fd, const std::string& data) {
    char hdr[32];
    snprintf(hdr, sizeof(hdr), "%zx\r\n", data.size());
    return send_all(fd, std::string(hdr) + data + "\r\n");
  }

  // Stream a decode as newline-delimited JSON chunks over chunked
  // transfer encoding: one {"token": N} line per emitted token AS THE
  // TICK EMITS IT, then a final {"done": true, "ids": [...], ...} line
  // (ids are the authoritative answer — under beam > 1 the streamed
  // tokens are the best hypothesis AT EACH TICK, provisional by
  // nature). A send failure marks the request cancelled; the scheduler
  // frees its slot at the next tick (no zombie carry). Returns the
  // keep-alive decision.
  bool stream_decode(int fd, const std::shared_ptr<DecodeReq>& r,
                     bool keep) {
    if (!send_all(fd,
                  std::string("HTTP/1.1 200 OK\r\n"
                              "Content-Type: application/x-ndjson\r\n"
                              "Transfer-Encoding: chunked\r\n"
                              "Connection: ") +
                      (keep ? "keep-alive" : "close") + "\r\n\r\n")) {
      r->cancelled = true;
      return false;
    }
    size_t sent = 0;
    std::unique_lock<std::mutex> l(r->mu);
    for (;;) {
      r->cv.wait(l, [&] { return r->done || r->out_ids.size() > sent; });
      while (sent < r->out_ids.size()) {
        int32_t tok = r->out_ids[sent];
        ++sent;
        l.unlock();
        bool ok = send_chunk(fd, "{\"token\":" + std::to_string(tok) +
                                     "}\n");
        if (ok)
          g_metrics.add("paddle_serving_stream_tokens_total", 1,
                        "tokens delivered to streaming clients");
        l.lock();
        if (!ok) {
          // client gone mid-stream: the sweep frees the slot next tick
          r->cancelled = true;
          return false;
        }
      }
      if (r->done) break;
    }
    std::string tail;
    if (!r->error.empty()) {
      tail = "{\"error\":\"" + ptpu::json_escape(r->error) +
             "\",\"status\":" + std::to_string(r->http_status) + "}\n";
    } else {
      std::ostringstream o;
      o << "{\"done\":true,\"ids\":[";
      const auto& ids = r->answer_ids();
      for (size_t i = 0; i < ids.size(); ++i)
        o << (i ? "," : "") << ids[i];
      o << "],\"ticks\":" << r->ticks << ",\"queued_s\":"
        << (r->t_start - r->t_enq) << ",\"continuous_admit\":"
        << (r->continuous_admit ? "true" : "false") << "}\n";
      tail = o.str();
    }
    l.unlock();
    if (!send_chunk(fd, tail)) return false;
    if (!send_all(fd, "0\r\n\r\n")) return false;
    return keep;
  }

  struct ScopedWork {
    std::atomic<int>& c;
    explicit ScopedWork(std::atomic<int>& c_) : c(c_) { ++c; }
    ~ScopedWork() { --c; }
  };

  // One request on a (possibly kept-alive) connection. Returns the
  // keep-alive decision: false closes the connection.
  bool handle(int fd, bool first, std::string* carry) {
    std::string method, path, body, model_hdr;
    double hdr_deadline_ms = 0;
    bool want_close = false;
    int rr = read_request(fd, &method, &path, &body, &hdr_deadline_ms,
                          &model_hdr, &want_close, carry, first);
    if (rr == 408) {
      g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                    "endpoint=\"http\"");
      respond(fd, 408, "{\"error\":\"client read timed out "
                       "(--io_timeout_ms)\"}");
      return false;
    }
    if (rr == 413) {
      g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                    "endpoint=\"http\"");
      respond(fd, 413, "{\"error\":\"request body exceeds "
                       "--max_body_bytes\"}");
      return false;
    }
    if (rr != 0) return false;
    const bool keep = !want_close && !stop;
    double t0 = now_s();
    if (path == "/healthz") {
      // liveness: the process is up AND the decode scheduler is not
      // wedged mid-tick (watchdog). Readiness lives at /readyz.
      if (!tick_live) {
        respond(fd, 503, "stalled: a decode tick exceeded --tick_hang_ms\n",
                "text/plain", "", keep);
        return keep;
      }
      respond(fd, 200, "ok\n", "text/plain", "", keep);
      return keep;
    }
    if (path == "/readyz") {
      if (!ready) {
        respond(fd, 503, "draining\n", "text/plain", "", keep);
        return keep;
      }
      // the ready body carries bundle_version + backend kind (JSON) so
      // a router / fleet publisher confirms a reload without a full
      // /metrics scrape; the status code stays the contract for old
      // probes (200 = ready). %.0f keeps large versions exact through
      // the double's 2^53 integer range (the /metrics fmt() lesson).
      auto B = cur_bundle();
      char rb[192];
      snprintf(rb, sizeof(rb),
               "{\"status\":\"ok\",\"bundle_version\":%.0f,"
               "\"backend\":\"%s\"}",
               B == nullptr ? 0.0 : B->version, backend.c_str());
      respond(fd, 200, rb, "application/json", "", keep);
      return keep;
    }
    if (path == "/metrics") {
      respond(fd, 200, g_metrics.prometheus(),
              "text/plain; version=0.0.4", "", keep);
      return keep;
    }
    if (path == "/metrics.json") {
      respond(fd, 200, g_metrics.json_snapshot(), "application/json", "",
              keep);
      return keep;
    }
    if (path == "/v1/signature") {
      g_metrics.add("paddle_serving_requests_total", 1, "requests served",
                    "endpoint=\"signature\"");
      if (!model_hdr.empty() && model_state(model_hdr) == nullptr) {
        respond(fd, 404, "{\"error\":\"unknown model '" +
                             ptpu::json_escape(model_hdr) + "\'\"}",
                "application/json", "", keep);
        return keep;
      }
      auto B = cur_bundle(model_hdr);
      respond(fd, 200, (B == nullptr || B->signature_json.empty())
                           ? "{}" : B->signature_json,
              "application/json", "", keep);
      return keep;
    }
    const bool is_work = method == "POST" &&
                         (path == "/v1/infer" || path == "/v1/decode" ||
                          path == "/v1/reload" || path == "/v1/rows");
    if (is_work && draining) {
      // graceful drain: admitted work completes, new work is turned
      // away while a load balancer reacts to /readyz going 503
      g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                    "endpoint=\"draining\"");
      respond(fd, 503, "{\"error\":\"draining: daemon is shutting down, "
                       "not accepting new work\"}",
              "application/json", "Retry-After: 1\r\n");
      return false;
    }
    if (path == "/v1/reload" && method == "POST") {
      ScopedWork w(active_work);
      g_metrics.add("paddle_serving_requests_total", 1, "requests served",
                    "endpoint=\"reload\"");
      // model routing: X-Model header, then the "model" body field,
      // then the default model — per-model reload isolation
      std::string model = model_hdr;
      std::string target;
      bool have_target = false;
      if (!body.empty()) {
        JParser jp{body.data(), body.data() + body.size()};
        JValue v = jp.parse();
        if (!jp.ok) {
          // a truncated deploy-script body must NOT silently reload
          // the old path and report success
          g_metrics.add("paddle_serving_errors_total", 1,
                        "request errors", "endpoint=\"reload\"");
          respond(fd, 400, "{\"error\":\"reload body is not valid JSON "
                           "(want {} or {\\\"bundle\\\": path})\"}",
                  "application/json", "", keep);
          return keep;
        }
        if (model.empty())
          if (const JValue* mv = v.get("model"))
            if (mv->kind == JValue::kStr) model = mv->str;
        if (const JValue* b = v.get("bundle")) {
          target = b->str;
          have_target = true;
        }
      }
      if (!have_target) target = cur_bundle_path(model);
      std::string msg;
      int code = do_reload(model, target, &msg);
      if (code != 200) {
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"reload\"");
        respond(fd, code,
                "{\"error\":\"" + ptpu::json_escape(msg) + "\"}",
                "application/json", "", keep);
      } else {
        respond(fd, 200, msg, "application/json", "", keep);
      }
      return keep;
    }
    if (path == "/v1/rows" && method == "POST") {
      // streamed row freshness: apply a PTPUDLT1 row delta
      // (host_table.write_row_delta) onto the live bundle's host row
      // store. EVERYTHING validates before anything mutates — a torn
      // or regressing delta 409s with the store untouched and the
      // daemon keeps serving the pre-delta rows.
      ScopedWork w(active_work);
      g_metrics.add("paddle_serving_requests_total", 1, "requests served",
                    "endpoint=\"rows\"");
      static const char* kDeltaHelp =
          "streamed row-delta applications (POST /v1/rows)";
      auto rows_error = [&](int code, const std::string& e) {
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"rows\"");
        g_metrics.add("paddle_serving_rowstore_deltas_total", 1,
                      kDeltaHelp, "result=\"rejected\"");
        respond(fd, code, "{\"error\":\"" + ptpu::json_escape(e) + "\"}",
                "application/json", "", keep);
        return keep;
      };
      JParser jp{body.data(), body.data() + body.size()};
      JValue v = jp.parse();
      if (!jp.ok)
        return rows_error(400, "request body is not valid JSON");
      std::string model = model_hdr;
      if (model.empty())
        if (const JValue* mv = v.get("model"))
          if (mv->kind == JValue::kStr) model = mv->str;
      const JValue* dv = v.get("delta");
      if (dv == nullptr || dv->kind != JValue::kStr || dv->str.empty())
        return rows_error(400, "body wants {\"delta\": path} (a "
                               "PTPUDLT1 row-delta file)");
      ModelState* ms = model_state(model);
      if (ms == nullptr)
        return rows_error(
            models.empty() ? 400 : 404,
            models.empty()
                ? "no bundle serves host tables (toy/decode-only daemon)"
                : "unknown model '" + model + "'");
      // full publish wins, deterministically: /v1/reload holds the same
      // per-model lock, so a delta never interleaves a bundle swap —
      // it applies to the live lineage or 409s against the new one
      std::lock_guard<std::mutex> rl(ms->reload_mu);
      auto B = cur_bundle(ms->name);
      if (B == nullptr || B->host_stores.empty())
        return rows_error(400, "model '" + ms->name +
                                   "' serves no host-resident tables");
      std::ifstream df(dv->str, std::ios::binary);
      if (!df.good())
        return rows_error(400, "cannot open row delta: " + dv->str);
      std::string dbuf((std::istreambuf_iterator<char>(df)),
                       std::istreambuf_iterator<char>());
      // chaos: stall mid-apply (the SIGKILL-during-delta window)
      if (const FaultSpec* f = g_faults.fire("rows.slow"))
        if (f->ms > 0)
          std::this_thread::sleep_for(
              std::chrono::microseconds(int64_t(f->ms * 1000)));
      std::string table;
      double base_version = 0;
      int64_t seq = 0, dwidth = 0, dvocab = 0;
      std::vector<int64_t> ids;
      std::vector<float> drows;
      std::string e = parse_row_delta(dbuf, &table, &base_version, &seq,
                                      &ids, &drows, &dwidth, &dvocab);
      if (!e.empty())
        return rows_error(409, "row delta rejected (store untouched): " +
                                   e);
      auto it = B->host_stores.find(table);
      if (it == B->host_stores.end())
        return rows_error(409, "row delta targets unknown host table '" +
                                   table + "'");
      HostRowStore* hs = it->second.get();
      if (dwidth != hs->width || dvocab != hs->vocab)
        return rows_error(
            409, "row delta geometry mismatch for table '" + table +
                     "': delta is vocab " + std::to_string(dvocab) +
                     " x width " + std::to_string(dwidth) +
                     ", store serves " + std::to_string(hs->vocab) +
                     " x " + std::to_string(hs->width));
      if (base_version != B->version) {
        char vb[192];
        snprintf(vb, sizeof(vb),
                 "delta base_version %.0f does not extend the live "
                 "bundle version %.0f — republish against the live "
                 "lineage",
                 base_version, B->version);
        return rows_error(409, vb);
      }
      int64_t cur = hs->cur_delta_seq();
      if (seq <= cur)
        return rows_error(409, "delta_seq regressed: store has applied " +
                                   std::to_string(cur) +
                                   ", delta carries " +
                                   std::to_string(seq));
      hs->apply_rows(ids, drows, seq);
      const std::string labels =
          "model=\"" + ms->name + "\",table=\"" + table + "\"";
      g_metrics.add("paddle_serving_rowstore_deltas_total", 1, kDeltaHelp,
                    "result=\"ok\"");
      g_metrics.add("paddle_serving_rowstore_delta_rows_total",
                    double(ids.size()),
                    "host-table rows replaced by streamed deltas",
                    labels);
      g_metrics.set("paddle_serving_rowstore_delta_seq", double(seq),
                    "last applied /v1/rows delta_seq (resets with a "
                    "full publish)", labels);
      char ob[256];
      snprintf(ob, sizeof(ob),
               "{\"result\":\"ok\",\"table\":\"%s\",\"rows\":%zu,"
               "\"delta_seq\":%lld,\"base_version\":%.0f}",
               ptpu::json_escape(table).c_str(), ids.size(),
               (long long)seq, base_version);
      respond(fd, 200, ob, "application/json", "", keep);
      return keep;
    }
    if (path == "/v1/infer" && method == "POST") {
      ScopedWork w(active_work);
      g_metrics.add("paddle_serving_requests_total", 1, "requests served",
                    "endpoint=\"infer\"");
      auto infer_error = [&](int code, const std::string& e) {
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"infer\"");
        respond(fd, code, "{\"error\":\"" + ptpu::json_escape(e) + "\"}",
                "application/json", "", keep);
        return keep;
      };
      JParser jp{body.data(), body.data() + body.size()};
      JValue v = jp.parse();
      if (!jp.ok) return infer_error(400, "request body is not valid JSON");
      // model routing: X-Model header wins, then the "model" body field,
      // then the default model (single-bundle daemons are unchanged)
      std::string model = model_hdr;
      if (model.empty())
        if (const JValue* mv = v.get("model"))
          if (mv->kind == JValue::kStr) model = mv->str;
      ModelState* ms = model_state(model);
      if (ms != nullptr)
        g_metrics.add("paddle_serving_requests_total", 1,
                      "requests served",
                      "endpoint=\"infer\",model=\"" + ms->name + "\"");
      if (!models.empty() && ms == nullptr)
        return infer_error(404, "unknown model '" + model + "'");
      // one immutable bundle snapshot per request: a concurrent reload
      // flips sessions BETWEEN requests, never mid-forward
      auto B = ms != nullptr ? cur_bundle(ms->name)
                             : std::shared_ptr<const BundleState>();
      if (!have_infer_backend(B.get()))
        return infer_error(400, "no infer backend (this daemon serves "
                                "decode only; start with --bundle)");
      const JValue* inputs = v.get("inputs");
      if (inputs == nullptr || inputs->kind != JValue::kObj)
        return infer_error(400, "body wants {\"inputs\": "
                                "{name: nested array, ...}}");
      std::vector<Feed> feeds;
      std::string err;
      if (!parse_infer_feeds(B.get(), *inputs, &feeds, &err))
        return infer_error(400, err);
      double dl_ms = hdr_deadline_ms;
      if (dl_ms <= 0)
        if (const JValue* dv = v.get("deadline_ms"))
          if (dv->kind == JValue::kNum) dl_ms = dv->num;
      if (batch_window_ms > 0 && ms != nullptr && B != nullptr &&
          !draining && !stop && ms->gather.joinable()) {
        // micro-batch path: enqueue into the model's gather window.
        // Shape key = feed names + dtypes + per-row extents; only
        // same-key requests coalesce (row concat is then exact).
        auto j = std::make_shared<InferJob>();
        j->t_enq = t0;
        if (dl_ms > 0) j->deadline = t0 + dl_ms / 1000.0;
        bool batchable = !feeds.empty();
        int64_t rows = -1;
        std::string key;
        for (const auto& f : feeds) {
          if (f.dims.empty() || f.dims[0] < 1) { batchable = false; break; }
          if (rows < 0) rows = f.dims[0];
          if (f.dims[0] != rows) { batchable = false; break; }
          key += f.name + (f.is_int ? "#i[" : "#f[");
          for (size_t d2 = 1; d2 < f.dims.size(); ++d2)
            key += (d2 > 1 ? "," : "") + std::to_string(f.dims[d2]);
          key += "]";
        }
        if (batchable && rows <= batch_cap(B.get())) {
          j->feeds = std::move(feeds);
          j->rows = rows;
          j->key = std::move(key);
          bool enqueued = false;
          {
            std::lock_guard<std::mutex> ql(ms->qmu);
            if (stop || draining) {
              // raced a drain: fall through to solo execution below
              feeds = std::move(j->feeds);
            } else if (ms->q.size() >= batch_max_queue) {
              g_metrics.add("paddle_serving_shed_total", 1,
                            "requests shed at admission",
                            "endpoint=\"infer\",model=\"" + ms->name +
                                "\"");
              g_metrics.add("paddle_serving_errors_total", 1,
                            "request errors", "endpoint=\"infer\"");
              respond(fd, 503,
                      "{\"error\":\"overloaded: infer batch queue above "
                      "--batch_max_queue\"}",
                      "application/json", "Retry-After: 1\r\n", keep);
              return keep;
            } else {
              ms->q.push_back(j);
              enqueued = true;
            }
          }
          if (enqueued) {
            ms->qcv.notify_one();
            j->wait();
            if (j->status != 200) {
              // the batcher already counted the error
              respond(fd, j->status,
                      "{\"error\":\"" + ptpu::json_escape(j->err) + "\"}",
                      "application/json", "", keep);
              return keep;
            }
            g_metrics.observe("paddle_serving_request_seconds",
                              now_s() - t0,
                              "end-to-end request latency (enqueue to "
                              "completion)", "endpoint=\"infer\"");
            respond(fd, 200, j->out, "application/json", "", keep);
            return keep;
          }
        }
        // shape not batchable (ragged rows / exceeds the row budget):
        // solo execution below
      }
      {
        int scode = 500;
        if (!stage_host_rows(B.get(),
                             ms != nullptr ? ms->name : default_model,
                             &feeds, &scode, &err))
          return infer_error(scode, err);
      }
      charge_exec();
      std::string out = infer_feeds(B.get(), feeds, &err);
      if (out.empty()) return infer_error(400, err);
      g_metrics.observe("paddle_serving_request_seconds", now_s() - t0,
                        "end-to-end request latency (enqueue to "
                        "completion)", "endpoint=\"infer\"");
      respond(fd, 200, out, "application/json", "", keep);
      return keep;
    }
    if (path == "/v1/decode" && method == "POST") {
      ScopedWork w(active_work);
      g_metrics.add("paddle_serving_requests_total", 1, "requests served",
                    "endpoint=\"decode\"");
      if (!sched.backend) {
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"decode\"");
        respond(fd, 400,
                "{\"error\":\"no decode backend (start with --backend "
                "toy or a decode-capable bundle)\"}",
                "application/json", "", keep);
        return keep;
      }
      JParser jp{body.data(), body.data() + body.size()};
      JValue v = jp.parse();
      const JValue* src = jp.ok ? v.get("src") : nullptr;
      const JValue* inputs = jp.ok ? v.get("inputs") : nullptr;
      bool have_src = src != nullptr && src->kind == JValue::kArr &&
                      !src->arr.empty();
      bool have_inputs = inputs != nullptr &&
                         inputs->kind == JValue::kObj;
      if (!have_src && !have_inputs) {
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"decode\"");
        respond(fd, 400, "{\"error\":\"body wants {\\\"src\\\": "
                         "[ids...], \\\"max_new\\\": n} or "
                         "{\\\"inputs\\\": {name: row, ...}}\"}",
                "application/json", "", keep);
        return keep;
      }
      auto r = std::make_shared<DecodeReq>();
      if (have_src)
        for (const auto& e : src->arr) r->src.push_back(int32_t(e.num));
      if (have_inputs) {
        // bundle decode backends: per-request typed feed rows (same
        // shape as one slot row of the recorded init signature)
        auto B = cur_bundle();
        for (const auto& [name, jv] : inputs->obj) {
          Feed f;
          f.name = name;
          std::vector<double> flat;
          if (!flatten_json(jv, &f.dims, &flat)) {
            g_metrics.add("paddle_serving_errors_total", 1,
                          "request errors", "endpoint=\"decode\"");
            respond(fd, 400, "{\"error\":\"input '" +
                                 ptpu::json_escape(name) +
                                 "': not a rectangular nested array\"}",
                    "application/json", "", keep);
            return keep;
          }
          if (B != nullptr)
            for (const auto& fdn : B->feed_defs)
              if (fdn.name == name)
                f.is_int = fdn.kind == "index";
          if (f.is_int)
            for (double d2 : flat) f.i32.push_back(int32_t(d2));
          else
            for (double d2 : flat) f.f32.push_back(float(d2));
          r->feeds.push_back(std::move(f));
        }
      }
      std::string perr = sched.backend->prepare(r.get());
      if (!perr.empty()) {
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"decode\"");
        respond(fd, 400,
                "{\"error\":\"" + ptpu::json_escape(perr) + "\"}",
                "application/json", "", keep);
        return keep;
      }
      if (const JValue* mn = v.get("max_new")) r->max_new = int(mn->num);
      // the cap applies whether or not the client sent the field — it
      // is the operator's latency/admission bound
      r->max_new = std::max(1, std::min(r->max_new, max_new_cap));
      if (const JValue* stv = v.get("stream"))
        r->stream = stv->kind == JValue::kBool ? stv->b : stv->num != 0;
      // deadline priority: X-Deadline-Ms header, then the body field,
      // then --default_deadline_ms; 0 = unbounded
      double dl_ms = hdr_deadline_ms;
      if (dl_ms <= 0)
        if (const JValue* d2 = v.get("deadline_ms")) dl_ms = d2->num;
      if (dl_ms <= 0) dl_ms = default_deadline_ms;
      if (dl_ms > 0) r->deadline = now_s() + dl_ms / 1000.0;
      switch (sched.submit(r)) {
        case Scheduler::kOk:
          break;
        case Scheduler::kShed:
          g_metrics.add("paddle_serving_shed_total", 1,
                        "requests load-shed above --queue_high_water");
          g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                        "endpoint=\"decode\"");
          respond(fd, 503, "{\"error\":\"overloaded: decode queue above "
                           "its high-water mark\"}",
                  "application/json", "Retry-After: 1\r\n", keep);
          return keep;
        case Scheduler::kFull:
          g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                        "endpoint=\"decode\"");
          respond(fd, 503, "{\"error\":\"decode queue full\"}",
                  "application/json", "Retry-After: 1\r\n", keep);
          return keep;
        case Scheduler::kShutdown:
          g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                        "endpoint=\"decode\"");
          respond(fd, 503, "{\"error\":\"daemon shutting down\"}");
          return false;
      }
      if (r->stream) return stream_decode(fd, r, keep);
      r->wait();
      if (!r->error.empty()) {
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"decode\"");
        respond(fd, r->http_status >= 400 ? r->http_status : 503,
                "{\"error\":\"" + ptpu::json_escape(r->error) + "\"}",
                "application/json", "", keep);
        return keep;
      }
      std::ostringstream o;
      o << "{\"ids\":[";
      const auto& ids = r->answer_ids();
      for (size_t i = 0; i < ids.size(); ++i)
        o << (i ? "," : "") << ids[i];
      o << "],\"ticks\":" << r->ticks << ",\"queued_s\":"
        << (r->t_start - r->t_enq) << ",\"continuous_admit\":"
        << (r->continuous_admit ? "true" : "false") << "}";
      respond(fd, 200, o.str(), "application/json", "", keep);
      return keep;
    }
    respond(fd, 404, "{\"error\":\"no such endpoint\"}", "application/json",
            "", keep);
    return keep;
  }

  // ---- graceful drain + ordered shutdown ----

  // Step 1 (SIGTERM): flip readiness so load balancers stop routing,
  // refuse new work with 503, keep every admitted request running.
  void begin_drain() {
    ready = false;
    draining = true;
    if (sched.backend) sched.begin_drain();
    // cut every open gather window NOW: a partially-gathered batch is
    // flushed (executed + answered), never dropped on the floor
    for (auto& [mname, ms] : models) ms->qcv.notify_all();
    g_metrics.set("paddle_serving_ready", 0,
                  "1 while accepting new work (0 once draining)");
    g_metrics.set("paddle_serving_draining", 1,
                  "1 while a graceful drain is in progress");
  }

  // Step 2: wait (bounded by --drain_timeout_s) until every admitted
  // request finished — queued decodes included. True = clean drain;
  // false = budget expired, the hard stop will 503 the remainder.
  bool wait_drained(double timeout_s) {
    double deadline = now_s() + timeout_s;
    while (now_s() < deadline) {
      bool conns_empty;
      {
        std::lock_guard<std::mutex> l(conn_mu);
        conns_empty = conns.empty();
      }
      bool sched_idle = !sched.backend || sched.idle();
      if (conns_empty && sched_idle && active_work.load() == 0)
        return true;
      std::this_thread::sleep_for(std::chrono::milliseconds(10));
    }
    return false;
  }

  // Step 3a: wake serve() out of poll so its thread can be joined
  // (the caller owns that thread and must join it before step 3b
  // closes the pipe fds).
  void stop_accepting() {
    if (stop_pipe[1] >= 0) {
      char c = 'q';
      (void)!write(stop_pipe[1], &c, 1);
    }
  }

  // Step 3b: ordered teardown — the fix for the documented
  // pthread_cond_destroy-under-waiters hang that used to force _exit:
  // hard-stop + join the scheduler (letting it 503 anything the drain
  // budget left behind), then stop + join the workers and watchdog so
  // no thread waits on any condvar when destructors run. Call with the
  // serve() thread already joined.
  void shutdown_ordered() {
    if (sched.backend) sched.shutdown();
    {
      std::lock_guard<std::mutex> l(conn_mu);
      stop = true;
    }
    conn_cv.notify_all();
    // the batchers flush their final windows first (workers may be
    // parked in InferJob::wait; every queued job gets finished) —
    // enqueue re-checks `stop` under qmu, so nothing lands after the
    // flush
    for (auto& [mname, ms] : models) {
      ms->qcv.notify_all();
      if (ms->gather.joinable()) ms->gather.join();
    }
    for (auto& w : workers) w.join();
    workers.clear();
    if (watchdog.joinable()) watchdog.join();
    if (listen_fd >= 0) { close(listen_fd); listen_fd = -1; }
    for (int i = 0; i < 2; ++i)
      if (stop_pipe[i] >= 0) { close(stop_pipe[i]); stop_pipe[i] = -1; }
  }

  // ---- /v1/infer over the execution backends ----

  static bool have_infer_backend(const BundleState* B) {
    return B != nullptr && (B->engine != nullptr || B->pjrt != nullptr);
  }

  // Flatten an already-parsed {"inputs": {...}} object into typed
  // feeds (Feed: the shared typed-request form). False + *err on a
  // malformed payload.
  static bool parse_infer_feeds(const BundleState* B, const JValue& inputs,
                                std::vector<Feed>* feeds,
                                std::string* err) {
    for (const auto& [name, jv] : inputs.obj) {
      Feed f;
      f.name = name;
      std::vector<double> flat;
      if (!flatten_json(jv, &f.dims, &flat)) {
        *err = "input '" + name + "': not a rectangular nested array";
        return false;
      }
      std::string base = name;
      if (base.size() > 5 && base.compare(base.size() - 5, 5, ":mask") == 0)
        base = base.substr(0, base.size() - 5);
      for (const auto& fd : B->feed_defs)
        if (fd.name == base)
          f.is_int = (fd.kind == "index") && base == name;
      if (f.is_int)
        for (double d : flat) f.i32.push_back(int32_t(d));
      else
        for (double d : flat) f.f32.push_back(float(d));
      feeds->push_back(std::move(f));
    }
    return true;
  }

  // Stage host-resident rows for one request (solo path) or one
  // gathered window (exec_batch): extract the distinct ids from each
  // table's claimed id feeds, remap those feeds IN PLACE to slot
  // space, gather the touched [slots, D] rows from the mmap'd store,
  // and append them as the '<table>:rows' feed the interp engine's
  // embedding branch / the exported module's host_rows input consumes.
  // On pjrt the slab is padded to the exported row budget R (the
  // module input's static leading dim); a request touching more than
  // R rows is refused 400 — with the default exported budget that can
  // only happen to a request already exceeding the batch shapes.
  // False + *code/*err on failure (400 malformed/oversized, 500 store
  // corruption).
  bool stage_host_rows(const BundleState* B, const std::string& model,
                       std::vector<Feed>* feeds, int* code,
                       std::string* err) {
    if (B == nullptr || B->host_stores.empty()) return true;
    for (const auto& [tname, hs] : B->host_stores) {
      double t0 = now_s();
      const std::string rows_name = tname + ":rows";
      for (const auto& f : *feeds)
        if (f.name == rows_name) {
          *code = 400;
          *err = "input '" + rows_name +
                 "' is reserved for staged host-table rows";
          return false;
        }
      // the table's claimed id feeds present in this request
      std::vector<Feed*> claimed;
      for (auto& f : *feeds)
        for (const auto& cf : hs->feeds)
          if (f.name == cf && f.is_int) claimed.push_back(&f);
      // distinct touched ids -> dense slot space (sorted: the gather
      // below writes consecutive rows in sorted-id order)
      std::map<int32_t, int32_t> slot;
      for (Feed* f : claimed)
        for (int32_t v : f->i32) slot[v] = 0;
      int64_t touched = int64_t(slot.size());
      int64_t lead = std::max<int64_t>(touched, 1);
      if (backend == "pjrt" && B->pjrt != nullptr) {
        int64_t budget = 0;
        for (const auto& io : B->sig_inputs)
          if (io.name == rows_name && !io.dims.empty())
            budget = io.dims[0];
        if (budget <= 0) {
          *code = 400;
          *err = "bundle's module has no '" + rows_name +
                 "' host-rows input (re-export with the row sidecar "
                 "enabled)";
          return false;
        }
        if (touched > budget) {
          *code = 400;
          *err = "request touches " + std::to_string(touched) +
                 " rows of host table '" + tname +
                 "', exceeding the exported host-row budget " +
                 std::to_string(budget) + "; split the request";
          return false;
        }
        lead = budget;
      }
      int32_t next = 0;
      std::vector<int64_t> ids;
      ids.reserve(size_t(touched));
      for (auto& kv : slot) {
        kv.second = next++;
        ids.push_back(int64_t(kv.first));
      }
      std::vector<float> rows(size_t(lead) * size_t(hs->width), 0.0f);
      std::string e = hs->gather(ids, rows.data());
      if (!e.empty()) {
        *code = 500;
        *err = e;
        return false;
      }
      for (Feed* f : claimed)
        for (auto& v : f->i32) v = slot[v];
      Feed staged;
      staged.name = rows_name;
      staged.is_int = false;
      staged.dims = {lead, hs->width};
      staged.f32 = std::move(rows);
      feeds->push_back(std::move(staged));
      const std::string labels =
          "model=\"" + model + "\",table=\"" + tname + "\"";
      g_metrics.observe(
          "paddle_serving_rowstore_stage_seconds", now_s() - t0,
          "time to extract, gather and remap one request's touched "
          "host-table rows", labels);
      g_metrics.observe_buckets(
          "paddle_serving_rowstore_staged_rows", double(touched),
          "distinct host-table rows staged per execute",
          {1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096, 16384,
           65536},
          labels);
      g_metrics.set("paddle_serving_rowstore_hit_rate", hs->hit_rate(),
                    "cumulative row-cache/overlay hit fraction of host "
                    "row lookups", labels);
      g_metrics.set("paddle_serving_rowstore_resident_bytes",
                    hs->resident_bytes(),
                    "resident row bytes (LRU cache bounded by "
                    "--host_cache_rows, plus the /v1/rows delta "
                    "overlay)", labels);
    }
    return true;
  }

  // Run the interp engine's n-ary typed call over feeds; fills
  // *results/*bufs. Returns the output count, or -1 with *err set.
  int interp_execute(const BundleState* B, std::vector<Feed>& feeds,
                     std::vector<ptpu_pjrt_tensor>* results,
                     std::vector<std::vector<uint8_t>>* bufs,
                     std::string* err) {
    std::vector<const char*> names;
    std::vector<ptpu_pjrt_tensor> args(feeds.size());
    for (size_t i = 0; i < feeds.size(); ++i) {
      Feed& f = feeds[i];
      names.push_back(f.name.c_str());
      memset(&args[i], 0, sizeof(args[i]));
      args[i].dtype = f.is_int ? PTPU_DT_I32 : PTPU_DT_F32;
      args[i].rank = int32_t(f.dims.size());
      for (size_t d = 0; d < f.dims.size(); ++d) args[i].dims[d] = f.dims[d];
      args[i].data = f.is_int ? (void*)f.i32.data() : (void*)f.f32.data();
      args[i].size_bytes =
          int64_t((f.is_int ? f.i32.size() : f.f32.size()) * 4);
    }
    int n_out = ptpu_engine_num_outputs(B->engine);
    if (n_out < 0) {
      *err = "no interp engine for this request (pjrt-only daemon?)";
      return -1;
    }
    results->assign(static_cast<size_t>(n_out), ptpu_pjrt_tensor{});
    bufs->assign(static_cast<size_t>(n_out), {});
    for (int attempt = 0; attempt < 2; ++attempt) {
      for (int i = 0; i < n_out; ++i) {
        // modest first guess; the -2 retry reports exact sizes
        if ((*bufs)[i].empty()) (*bufs)[i].resize(64 << 10);
        memset(&(*results)[i], 0, sizeof((*results)[i]));
        (*results)[i].data = (*bufs)[i].data();
        (*results)[i].size_bytes = int64_t((*bufs)[i].size());
      }
      int rc = ptpu_engine_forward_n(B->engine, names.data(), args.data(),
                                     int32_t(args.size()),
                                     results->data(), int32_t(n_out));
      if (rc == -2) {
        for (int i = 0; i < n_out; ++i)
          (*bufs)[i].assign(size_t((*results)[i].size_bytes) + 1, 0);
        continue;
      }
      if (rc != 0) {
        *err = ptpu_engine_last_error();
        return -1;
      }
      return n_out;
    }
    *err = "output capacity retry did not settle";
    return -1;
  }

  // The classic per-request path: execute typed feeds on the resolved
  // backend and emit the response JSON.
  std::string infer_feeds(const BundleState* B, std::vector<Feed>& feeds,
                          std::string* err) {
    if (backend == "pjrt") return infer_pjrt(B, feeds, err);
    std::vector<ptpu_pjrt_tensor> results;
    std::vector<std::vector<uint8_t>> bufs;
    int n_out = interp_execute(B, feeds, &results, &bufs, err);
    if (n_out < 0) return "";
    return emit_outputs(results, bufs, n_out, [B](int i) {
      return std::string(ptpu_engine_output_name(B->engine, i));
    });
  }

  // Emit the {"outputs": {...}} response JSON. With rows >= 0 the
  // batched scatter path: outputs whose leading dim equals total_rows
  // are sliced to [row_off, row_off + rows) — a request in a coalesced
  // window reads back exactly its own rows, bit-identical to a solo
  // execute. rows < 0 emits every tensor whole (the per-request path).
  template <typename NameFn>
  std::string emit_outputs(const std::vector<ptpu_pjrt_tensor>& results,
                           const std::vector<std::vector<uint8_t>>& bufs,
                           int n_out, NameFn name_of, int64_t row_off = 0,
                           int64_t rows = -1, int64_t total_rows = -1) {
    std::ostringstream o;
    o << "{\"outputs\":{";
    for (int i = 0; i < n_out; ++i) {
      const ptpu_pjrt_tensor& r = results[i];
      bool slice = rows >= 0 && r.rank >= 1 && r.dims[0] == total_rows;
      o << (i ? "," : "") << '"' << ptpu::json_escape(name_of(i))
        << "\":{\"shape\":[";
      int64_t n = 1;
      for (int32_t d = 0; d < r.rank; ++d) {
        o << (d ? "," : "")
          << (d == 0 && slice ? rows : r.dims[d]);
        n *= r.dims[d];
      }
      o << "],\"data\":[";
      int64_t per = slice ? n / std::max<int64_t>(total_rows, 1) : 0;
      int64_t j0 = slice ? row_off * per : 0;
      int64_t j1 = slice ? (row_off + rows) * per : n;
      const uint8_t* raw = bufs[i].data();
      for (int64_t j = j0; j < j1; ++j) {
        if (j != j0) o << ',';
        char b[40];
        switch (r.dtype) {
          case PTPU_DT_I32:
            o << reinterpret_cast<const int32_t*>(raw)[j];
            break;
          case PTPU_DT_I64:
            o << (long long)reinterpret_cast<const int64_t*>(raw)[j];
            break;
          case PTPU_DT_PRED:
          case PTPU_DT_U8:
            o << int(raw[j]);
            break;
          case PTPU_DT_F64:
            snprintf(b, sizeof(b), "%.12g",
                     reinterpret_cast<const double*>(raw)[j]);
            o << b;
            break;
          default:
            snprintf(b, sizeof(b), "%.8g",
                     reinterpret_cast<const float*>(raw)[j]);
            o << b;
        }
      }
      o << "]}";
    }
    o << "}}";
    return o.str();
  }

  // Execute signature-ordered typed args on the pjrt runner. The exec
  // batch E is the bucket shape: with use_ladder the smallest rung >=
  // req_batch among the compiled ladder programs and the static-batch
  // main module; without it always the main module at its exported
  // static batch (the classic per-request semantics). Requests shorter
  // than E are zero-padded up and the results sliced back to
  // req_batch. Returns the output count (results/bufs filled, leading
  // dims already trimmed), or -1 with *err. *padded_to reports E for
  // the pad-fraction metric.
  int pjrt_execute(const BundleState* B, const std::vector<Feed>& feeds,
                   int64_t req_batch, bool use_ladder,
                   std::vector<ptpu_pjrt_tensor>* results,
                   std::vector<std::vector<uint8_t>>* bufs,
                   int64_t* padded_to, std::string* err) {
    const int sig_static_batch = B->sig_static_batch;
    if (B->sig_inputs.empty()) {
      *err = "bundle has no recorded signature";
      return -1;
    }
    // bucket pick: smallest compiled shape that fits the batch
    int64_t E = sig_static_batch;
    int prog = -1;   // -1 = the main module (program 0)
    if (use_ladder) {
      bool fits = E >= req_batch;
      for (const auto& [rung, p] : B->ladder)
        if (rung >= req_batch && (!fits || rung < E)) {
          E = rung;
          prog = p;
          fits = true;
        }
      if (!fits) {
        *err = "batch " + std::to_string(req_batch) +
               " exceeds every exported batch shape";
        return -1;
      }
    }
    *padded_to = E;
    std::vector<std::vector<uint8_t>> arg_store;
    std::vector<ptpu_pjrt_tensor> args;
    for (const auto& io : B->sig_inputs) {
      const Feed* f = nullptr;
      for (const auto& c : feeds)
        if (c.name == io.name) f = &c;
      if (f == nullptr) {
        *err = "missing input '" + io.name + "'";
        return -1;
      }
      if (io.dims.empty()) {
        *err = "signature input '" + io.name + "' has no dims";
        return -1;
      }
      // host_rows inputs carry the staged row budget R as their
      // leading dim — a table shape, not a batch shape: never scaled
      // with the exec batch and never measured against req_batch
      const bool host_in = B->host_row_inputs.count(io.name) != 0;
      // scale the leading dim of batch-carrying inputs from the
      // recorded static batch to the chosen bucket shape
      int64_t io_lead =
          !host_in && io.dims[0] == sig_static_batch ? E : io.dims[0];
      if (!host_in && req_batch > io_lead) {
        *err = "request batch " + std::to_string(req_batch) +
               " exceeds the exported static batch " +
               std::to_string(io_lead) + "; split the request";
        return -1;
      }
      int64_t row = 1;
      for (size_t d = 1; d < io.dims.size(); ++d) row *= io.dims[d];
      int64_t isz = io.dtype == PTPU_DT_I64 ? 8
                    : io.dtype == PTPU_DT_PRED ? 1
                                               : 4;
      std::vector<uint8_t> buf(size_t(io_lead * row * isz), 0);
      int64_t rows = host_in ? io_lead
                             : std::min<int64_t>(req_batch, io_lead);
      // validate the client payload against what the copy below reads:
      // every feed must carry req_batch rows of the signature's
      // per-row extent (the interp path's size check, mirrored here);
      // staged host rows arrive padded to exactly R by the stager
      int64_t f_elems =
          int64_t(f->is_int ? f->i32.size() : f->f32.size());
      int64_t f_batch = f->dims.empty() ? 0 : f->dims[0];
      int64_t want_batch = host_in ? io_lead : req_batch;
      if (f_batch != want_batch || f_elems != want_batch * row) {
        *err = "input '" + io.name + "': expected " +
               std::to_string(want_batch) + " rows x " +
               std::to_string(row) + " elements (got batch " +
               std::to_string(f_batch) + ", " + std::to_string(f_elems) +
               " elements)";
        return -1;
      }
      for (int64_t r = 0; r < rows; ++r) {
        uint8_t* dst = buf.data() + size_t(r * row * isz);
        if (io.dtype == PTPU_DT_I32 && f->is_int)
          memcpy(dst, f->i32.data() + r * row, size_t(row * 4));
        else if (io.dtype == PTPU_DT_I32)
          for (int64_t j = 0; j < row; ++j)
            reinterpret_cast<int32_t*>(dst)[j] =
                int32_t(f->f32[size_t(r * row + j)]);
        else if (f->is_int)
          for (int64_t j = 0; j < row; ++j)
            reinterpret_cast<float*>(dst)[j] =
                float(f->i32[size_t(r * row + j)]);
        else
          memcpy(dst, f->f32.data() + r * row, size_t(row * 4));
      }
      ptpu_pjrt_tensor t;
      memset(&t, 0, sizeof(t));
      t.dtype = io.dtype;
      t.rank = int32_t(io.dims.size());
      for (size_t d = 0; d < io.dims.size(); ++d) t.dims[d] = io.dims[d];
      t.dims[0] = io_lead;
      t.data = buf.data();
      t.size_bytes = int64_t(buf.size());
      arg_store.push_back(std::move(buf));
      t.data = arg_store.back().data();
      args.push_back(t);
    }
    int n_out = prog >= 0 ? ptpu_pjrt_num_outputs_prog(B->pjrt, prog)
                          : ptpu_pjrt_num_outputs(B->pjrt);
    results->assign(static_cast<size_t>(std::max(n_out, 0)),
                    ptpu_pjrt_tensor{});
    bufs->assign(static_cast<size_t>(std::max(n_out, 0)), {});
    std::lock_guard<std::mutex> l(g_pjrt_device_mu);
    for (int attempt = 0; attempt < 2; ++attempt) {
      for (int i = 0; i < n_out; ++i) {
        if ((*bufs)[i].empty()) {
          // exact size from the recorded signature when available; the
          // -2 retry covers anything it under-estimates
          size_t cap = 64 << 10;
          if (i < int(B->sig_outputs.size())) {
            const SigIO& so = B->sig_outputs[size_t(i)];
            int64_t e = 1;
            for (size_t d2 = 1; d2 < so.dims.size(); ++d2)
              e *= so.dims[d2];
            e *= so.dims.empty() ? 1
                 : so.dims[0] == sig_static_batch ? E : so.dims[0];
            int64_t osz = so.dtype == PTPU_DT_I64 ? 8
                          : so.dtype == PTPU_DT_PRED ? 1
                                                     : 4;
            cap = size_t(std::max<int64_t>(e * osz, 16));
          }
          (*bufs)[i].resize(cap);
        }
        memset(&(*results)[i], 0, sizeof((*results)[i]));
        (*results)[i].data = (*bufs)[i].data();
        (*results)[i].size_bytes = int64_t((*bufs)[i].size());
      }
      int rc = prog >= 0
                   ? ptpu_pjrt_execute_prog(B->pjrt, prog, args.data(),
                                            int32_t(args.size()),
                                            results->data(),
                                            int32_t(n_out))
                   : ptpu_pjrt_execute_n(B->pjrt, args.data(),
                                         int32_t(args.size()),
                                         results->data(), int32_t(n_out));
      if (rc == -2) {
        for (int i = 0; i < n_out; ++i)
          (*bufs)[i].assign(size_t((*results)[i].size_bytes) + 1, 0);
        continue;
      }
      if (rc != 0) {
        *err = ptpu_pjrt_last_error();
        return -1;
      }
      // slice the zero-padding rows back out: results whose leading dim
      // is the exec batch are trimmed to the request batch (row-major,
      // so the real rows are the prefix)
      for (int i = 0; i < n_out; ++i)
        if ((*results)[i].rank >= 1 && E > 0 &&
            (*results)[i].dims[0] == E && req_batch < E)
          (*results)[i].dims[0] = req_batch;
      return n_out;
    }
    *err = "output capacity retry did not settle";
    return -1;
  }

  std::string infer_pjrt(const BundleState* B, std::vector<Feed>& feeds,
                         std::string* err) {
    // the per-request path executes the main module at its exported
    // static batch, exactly as before the micro-batcher existed
    int64_t req_batch = -1;
    for (const auto& io : B->sig_inputs) {
      if (B->host_row_inputs.count(io.name) != 0)
        continue;   // a staged table's leading dim is R, not the batch
      for (const auto& c : feeds)
        if (c.name == io.name && req_batch < 0)
          req_batch = c.dims.empty() ? 0 : c.dims[0];
      if (req_batch >= 0) break;
    }
    if (req_batch < 0 && !B->sig_inputs.empty()) {
      *err = "missing input '" + B->sig_inputs[0].name + "'";
      return "";
    }
    std::vector<ptpu_pjrt_tensor> results;
    std::vector<std::vector<uint8_t>> bufs;
    int64_t padded_to = 0;
    int n_out = pjrt_execute(B, feeds, req_batch, /*use_ladder=*/false,
                             &results, &bufs, &padded_to, err);
    if (n_out < 0) return "";
    return emit_outputs(results, bufs, n_out, [B](int i) {
      return i < int(B->sig_outputs.size())
                 ? B->sig_outputs[size_t(i)].name
                 : "out" + std::to_string(i);
    });
  }

  // ---- /v1/infer micro-batching (--batch_window_ms > 0) ----

  // Row budget of one batch execute: --batch_max, clamped on pjrt to
  // the largest compiled batch shape (ladder rung or static batch).
  int64_t batch_cap(const BundleState* B) const {
    int64_t cap = batch_max;
    if (backend == "pjrt" && B != nullptr) {
      int64_t best = B->sig_static_batch;
      for (const auto& [rung, p] : B->ladder)
        best = std::max<int64_t>(best, rung);
      if (best > 0) cap = std::min<int64_t>(cap, best);
    }
    return std::max<int64_t>(cap, 1);
  }

  // Concatenate the window's per-request feeds row-wise. Every job in
  // a window shares `key` (same feed order, dtypes, per-row extents),
  // so plain row concatenation is exact.
  static std::vector<Feed> concat_feeds(
      const std::vector<std::shared_ptr<InferJob>>& jobs) {
    std::vector<Feed> cat;
    for (size_t fi = 0; fi < jobs[0]->feeds.size(); ++fi) {
      Feed f;
      f.name = jobs[0]->feeds[fi].name;
      f.is_int = jobs[0]->feeds[fi].is_int;
      f.dims = jobs[0]->feeds[fi].dims;
      int64_t rows = 0;
      for (const auto& j : jobs) {
        const Feed& src = j->feeds[fi];
        rows += src.dims[0];
        f.i32.insert(f.i32.end(), src.i32.begin(), src.i32.end());
        f.f32.insert(f.f32.end(), src.f32.begin(), src.f32.end());
      }
      f.dims[0] = rows;
      cat.push_back(std::move(f));
    }
    return cat;
  }

  void finish_expired(ModelState* ms, const std::shared_ptr<InferJob>& j) {
    j->status = 504;
    j->err = "deadline expired inside the batch gather window "
             "(--batch_window_ms)";
    g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                  "endpoint=\"infer\"");
    g_metrics.add("paddle_serving_batch_expired_total", 1,
                  "infer requests whose deadline expired inside a "
                  "gather window (answered 504)",
                  "model=\"" + ms->name + "\"");
    j->finish();
  }

  // Execute one gathered window: concatenate rows, run ONCE (interp:
  // native n-ary dynamic batch; pjrt: smallest ladder rung that fits,
  // zero-padded), scatter result rows back to their requests. Requests
  // whose deadline passed by execute time answer 504 individually —
  // the rest of the window is never stalled by them.
  // --infer_exec_us: a fixed SERIALIZED cost per infer execute — the
  // toy model of a single accelerator's dispatch queue, the infer twin
  // of --toy_tick_us on the decode side. The per-request path pays it
  // once per request; a gathered window pays it once per BATCH, which
  // isolates the batcher's amortization the way --toy_tick_us isolates
  // admission.
  void charge_exec() {
    if (infer_exec_us <= 0) return;
    std::lock_guard<std::mutex> l(exec_dev_mu);
    std::this_thread::sleep_for(
        std::chrono::microseconds(infer_exec_us));
  }

  void exec_batch(ModelState* ms,
                  std::vector<std::shared_ptr<InferJob>>& jobs) {
    // chaos: stall the gathered window before it executes
    if (const FaultSpec* f = g_faults.fire("batch.window"))
      if (f->ms > 0)
        std::this_thread::sleep_for(
            std::chrono::microseconds(int64_t(f->ms * 1000)));
    double now = now_s();
    std::vector<std::shared_ptr<InferJob>> live;
    for (auto& j : jobs) {
      if (j->deadline > 0 && now >= j->deadline) finish_expired(ms, j);
      else live.push_back(j);
    }
    if (live.empty()) return;
    auto B = cur_bundle(ms->name);
    int64_t rows = 0;
    for (const auto& j : live) rows += j->rows;
    const std::string mlabel = "model=\"" + ms->name + "\"";
    g_metrics.observe_buckets(
        "paddle_serving_batch_size", double(live.size()),
        "infer requests coalesced per micro-batch execute",
        {1, 2, 4, 8, 16, 32, 64, 128, 256}, mlabel);
    g_metrics.add("paddle_serving_batches_total", 1,
                  "infer micro-batch executes", mlabel);
    for (const auto& j : live)
      g_metrics.observe("paddle_serving_batch_window_wait_seconds",
                        now - j->t_enq,
                        "time an infer request waited in the gather "
                        "window before executing", mlabel);
    std::string err;
    std::vector<Feed> cat = concat_feeds(live);
    // staging AFTER concat: the whole window's touched ids dedup into
    // one slot space, so a row shared across gathered requests stages
    // once. A staging failure fails the window below (n_out < 0).
    int stage_code = 500;
    (void)stage_code;   // window failures all answer 500
    bool staged =
        stage_host_rows(B.get(), ms->name, &cat, &stage_code, &err);
    charge_exec();                 // ONE dispatch for the whole window
    std::vector<ptpu_pjrt_tensor> results;
    std::vector<std::vector<uint8_t>> bufs;
    int n_out = -1;
    int64_t padded_to = rows;
    if (!staged) {
      n_out = -1;   // err already set by stage_host_rows
    }
    else if (backend == "pjrt" && B != nullptr && B->pjrt != nullptr)
      n_out = pjrt_execute(B.get(), cat, rows, /*use_ladder=*/true,
                           &results, &bufs, &padded_to, &err);
    else if (B != nullptr && B->engine != nullptr)
      n_out = interp_execute(B.get(), cat, &results, &bufs, &err);
    else
      err = "no infer backend for this model";
    double pad = padded_to > 0
                     ? double(padded_to - rows) / double(padded_to)
                     : 0;
    g_metrics.observe_buckets(
        "paddle_serving_batch_pad_fraction", pad,
        "fraction of executed rows that were padding (pjrt bucket "
        "rounding; 0 on the natively dynamic interp backend)",
        {0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0},
        mlabel);
    if (n_out < 0) {
      for (auto& j : live) {
        j->status = 500;
        j->err = err;
        g_metrics.add("paddle_serving_errors_total", 1, "request errors",
                      "endpoint=\"infer\"");
        j->finish();
      }
      return;
    }
    auto name_of = [&](int i) -> std::string {
      if (backend == "pjrt" && B->pjrt != nullptr)
        return i < int(B->sig_outputs.size())
                   ? B->sig_outputs[size_t(i)].name
                   : "out" + std::to_string(i);
      return std::string(ptpu_engine_output_name(B->engine, i));
    };
    int64_t off = 0;
    for (auto& j : live) {
      j->out = emit_outputs(results, bufs, n_out, name_of, off, j->rows,
                            rows);
      off += j->rows;
      j->finish();
    }
  }

  // One model's gather thread: open a window at the first queued
  // request, coalesce shape-compatible requests until the window
  // bound — pulled EARLIER to the nearest gathered deadline, so p95
  // never pays more than --batch_window_ms and a deadline inside the
  // window executes the batch early instead of expiring the request —
  // or the row budget, or a drain/stop (a partially-gathered window is
  // FLUSHED, never dropped). Shape-incompatible requests stay queued
  // and open the next window immediately after.
  void batcher_loop(ModelState* ms) {
    for (;;) {
      {
        std::unique_lock<std::mutex> l(ms->qmu);
        ms->qcv.wait(l, [&] { return stop.load() || !ms->q.empty(); });
        if (ms->q.empty() && stop) return;
      }
      double window_end = now_s() + batch_window_ms / 1000.0;
      int64_t cap = batch_cap(cur_bundle(ms->name).get());
      std::vector<std::shared_ptr<InferJob>> batch;
      int64_t rows = 0;
      std::string key;
      std::unique_lock<std::mutex> l(ms->qmu);
      for (;;) {
        double now = now_s();
        for (auto it = ms->q.begin(); it != ms->q.end();) {
          auto j = *it;
          if (j->deadline > 0 && now >= j->deadline) {
            // expired while queued: individual 504, window unharmed
            it = ms->q.erase(it);
            finish_expired(ms, j);
            continue;
          }
          if ((key.empty() || j->key == key) && rows + j->rows <= cap) {
            if (key.empty()) key = j->key;
            batch.push_back(j);
            rows += j->rows;
            it = ms->q.erase(it);
            continue;
          }
          ++it;
        }
        if (batch.empty()) {
          if (stop && ms->q.empty()) return;
          break;   // everything expired: reopen on the next arrival
        }
        double cut = window_end;
        for (const auto& j : batch)
          if (j->deadline > 0 && j->deadline < cut) cut = j->deadline;
        now = now_s();
        if (now >= cut || rows >= cap || draining || stop) break;
        // nap until the cutoff (bounded so stop/drain stay responsive);
        // a new arrival notifies and re-enters the sweep above
        double nap = std::min(cut - now, 0.05);
        ms->qcv.wait_for(l, std::chrono::microseconds(
                                int64_t(std::max(nap, 0.0005) * 1e6)));
      }
      l.unlock();
      if (!batch.empty()) exec_batch(ms, batch);
    }
  }
};

// --- selftest (the `make serve-smoke` body) --------------------------------

std::string http_get(int port, const std::string& path,
                     const std::string& post_body = "",
                     const std::string& extra_headers = "") {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return "";
  sockaddr_in addr;
  memset(&addr, 0, sizeof(addr));
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = htons(uint16_t(port));
  if (connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
    close(fd);
    return "";
  }
  std::ostringstream o;
  // Connection: close — this helper reads to EOF; the daemon keeps
  // HTTP/1.1 connections alive by default since r19
  if (post_body.empty()) {
    o << "GET " << path << " HTTP/1.1\r\nHost: x\r\n"
      << "Connection: close\r\n" << extra_headers << "\r\n";
  } else {
    o << "POST " << path << " HTTP/1.1\r\nHost: x\r\n"
      << "Connection: close\r\n" << extra_headers
      << "Content-Length: " << post_body.size() << "\r\n\r\n" << post_body;
  }
  std::string req = o.str();
  send(fd, req.data(), req.size(), MSG_NOSIGNAL);
  std::string resp;
  char tmp[4096];
  ssize_t n;
  while ((n = recv(fd, tmp, sizeof(tmp), 0)) > 0) resp.append(tmp, size_t(n));
  close(fd);
  size_t p = resp.find("\r\n\r\n");
  return p == std::string::npos ? resp : resp.substr(p + 4);
}

int selftest(Daemon& d) {
  // spawn the server in-process on a free port, POST decode requests,
  // scrape /metrics — no Python, no external client. Tolerates
  // PTPU_SERVING_FAULTS being set (the chaos_sweep --serving grid runs
  // this body under every fault site): injected faults may turn
  // individual responses into 5xx, but every response must be
  // well-formed, the daemon must survive to answer a clean follow-up,
  // and the teardown must be the ordered one (exit 0, no _exit).
  d.backend = "toy";
  d.sched.backend.reset(new ToyBackend(d.slots, d.toy_hidden, d.toy_vocab,
                                         d.toy_tick_us));
  d.sched.drain_mode = d.drain_batch;
  d.sched.max_queue = d.max_queue;
  d.sched.high_water = d.queue_high_water;
  d.sched.start();
  std::string err;
  if (!d.start_listen(&err)) {
    fprintf(stderr, "selftest: %s\n", err.c_str());
    return 1;
  }
  if (!d.start_http()) {
    fprintf(stderr, "selftest: stop pipe failed\n");
    return 1;
  }
  std::thread srv([&d] { d.serve(); });
  // every exit from here on must run the ordered teardown: returning
  // with `srv` (or the workers) still live would std::terminate in a
  // joinable thread's destructor
  auto finish = [&](int rc) {
    d.begin_drain();
    d.wait_drained(5.0);
    d.stop_accepting();
    srv.join();
    d.shutdown_ordered();
    return rc;
  };
  std::string hz = http_get(d.port, "/healthz");
  std::string rz = http_get(d.port, "/readyz");
  if (hz.find("ok") != 0 || rz.find("\"status\":\"ok\"") == std::string::npos) {
    fprintf(stderr, "selftest: /healthz='%s' /readyz='%s'\n", hz.c_str(),
            rz.c_str());
    return finish(1);
  }
  // reload without a bundle must be a clean 400-class error, not a crash
  std::string rl = http_get(d.port, "/v1/reload", "{}");
  if (rl.find("error") == std::string::npos) {
    fprintf(stderr, "selftest: toy reload should error: %s\n", rl.c_str());
    return finish(1);
  }
  // a burst of concurrent decode requests exercises admission
  const int N = 12;
  std::vector<std::thread> ts;
  std::atomic<int> bad{0}, ok{0};
  for (int i = 0; i < N; ++i)
    ts.emplace_back([&, i] {
      std::ostringstream o;
      o << "{\"src\":[" << (i + 1) << "," << (i * 7 + 3)
        << "],\"max_new\":8}";
      std::string r = http_get(d.port, "/v1/decode", o.str());
      if (r.find("\"ids\":[") != std::string::npos) ok++;
      else if (r.find("\"error\"") == std::string::npos) bad++;
    });
  for (auto& t : ts) t.join();
  // the daemon survived whatever was injected: a clean request works
  std::string fin = http_get(d.port, "/v1/decode",
                             "{\"src\":[5,9],\"max_new\":8}");
  bool fin_ok = fin.find("\"ids\":[") != std::string::npos;
  std::string metrics = http_get(d.port, "/metrics");
  bool have = metrics.find("paddle_serving_decode_ticks_total") !=
              std::string::npos;
  if (bad > 0 || !fin_ok || !have) {
    fprintf(stderr, "selftest: bad=%d ok=%d final_ok=%d metrics_ok=%d\n%s\n",
            int(bad), int(ok), int(fin_ok), int(have), metrics.c_str());
    return finish(1);
  }
  // ordered shutdown: the same graceful-drain path SIGTERM takes —
  // this used to hang in pthread_cond_destroy under live waiters and
  // left via _exit; now every thread is joined before destructors run
  int rc = finish(0);
  printf("SERVE-SMOKE-OK port=%d requests=%d mode=%s faults=%zu\n", d.port,
         N, d.drain_batch ? "drain" : "continuous", g_faults.specs.size());
  return rc;
}

// --- signals ---------------------------------------------------------------
//
// SIGTERM/SIGINT start the graceful drain; SIGHUP hot-swaps parameters
// by re-reading the current --bundle path. Handlers only write one
// byte to a pipe (async-signal-safe); the main thread runs the actual
// drain/reload so no locks are ever taken in signal context.

int g_sig_pipe[2] = {-1, -1};

extern "C" void ptpu_serving_on_signal(int sig) {
  char c = sig == SIGHUP ? 'h' : 't';
  if (g_sig_pipe[1] >= 0) (void)!write(g_sig_pipe[1], &c, 1);
}

}  // namespace

int main(int argc, char** argv) {
  Daemon d;
  bool do_selftest = false;
  for (int i = 1; i < argc; ++i) {
    std::string a = argv[i];
    auto next = [&]() -> const char* {
      return i + 1 < argc ? argv[++i] : "";
    };
    if (a == "--bundle") {
      // `--bundle path` (single model, named "default") or repeated
      // `--bundle name=path` (multi-model daemon). A '/' before the
      // first '=' means the '=' belongs to the path, not a name.
      std::string spec = next();
      size_t eq = spec.find('=');
      if (eq != std::string::npos && eq > 0 &&
          spec.find('/') > eq) {
        d.bundle_specs.emplace_back(spec.substr(0, eq),
                                    spec.substr(eq + 1));
      } else {
        d.bundle_specs.emplace_back("default", spec);
      }
    }
    else if (a == "--port") d.port = atoi(next());
    else if (a == "--threads") d.threads = atoi(next());
    else if (a == "--backend") d.backend = next();
    else if (a == "--slots") d.slots = atoi(next());
    else if (a == "--drain_batch") d.drain_batch = true;
    else if (a == "--max_queue") d.max_queue = size_t(atoll(next()));
    else if (a == "--queue_high_water")
      d.queue_high_water = size_t(atoll(next()));
    else if (a == "--default_deadline_ms")
      d.default_deadline_ms = atof(next());
    else if (a == "--drain_timeout_s") d.drain_timeout_s = atof(next());
    else if (a == "--tick_hang_ms") d.tick_hang_ms = atof(next());
    else if (a == "--max_body_bytes") d.max_body_bytes = size_t(atoll(next()));
    else if (a == "--io_timeout_ms") d.io_timeout_ms = atoi(next());
    else if (a == "--toy_hidden") d.toy_hidden = atoi(next());
    else if (a == "--toy_vocab") d.toy_vocab = atoi(next());
    else if (a == "--toy_tick_us") d.toy_tick_us = atoi(next());
    else if (a == "--max_new_cap") d.max_new_cap = atoi(next());
    else if (a == "--batch_window_ms") d.batch_window_ms = atof(next());
    else if (a == "--batch_max") d.batch_max = atoi(next());
    else if (a == "--infer_exec_us") d.infer_exec_us = atoi(next());
    else if (a == "--batch_max_queue")
      d.batch_max_queue = size_t(atoll(next()));
    else if (a == "--host_cache_rows")
      d.host_cache_rows = size_t(atoll(next()));
    else if (a == "--pjrt_plugin") d.pjrt_plugin = next();
    else if (a == "--pjrt_options") d.pjrt_options = next();
    else if (a == "--pjrt_platform") d.pjrt_platform = next();
    else if (a == "--selftest") do_selftest = true;
    else if (a == "--help" || a == "-h") {
      printf(
          "paddle_tpu_serving --bundle model.ptpu [--port 0] [--threads N]\n"
          "  [--bundle name=path ...]  (repeat: multi-model daemon;\n"
          "   route with the X-Model header or a \"model\" body field)\n"
          "  [--backend auto|interp|pjrt|toy] [--slots N] [--drain_batch]\n"
          "  [--max_queue N] [--queue_high_water N] "
          "[--default_deadline_ms D]\n"
          "  [--batch_window_ms MS] [--batch_max ROWS] "
          "[--batch_max_queue N]\n"
          "   (infer micro-batching: coalesce queued /v1/infer requests\n"
          "    for up to MS ms — or until the nearest request deadline —\n"
          "    and execute once per window)\n"
          "  [--infer_exec_us US] (toy serialized per-execute cost —\n"
          "    the infer twin of --toy_tick_us, for batching A/Bs)\n"
          "  [--host_cache_rows N] (per host-resident table: LRU row\n"
          "    cache bound for mmap-backed meta.host_tables sidecars;\n"
          "    touched rows stage per request, POST /v1/rows streams\n"
          "    row deltas between full publishes)\n"
          "  [--drain_timeout_s S] [--tick_hang_ms MS] "
          "[--max_body_bytes N]\n"
          "  [--io_timeout_ms MS] [--pjrt_plugin libtpu.so] "
          "[--pjrt_options s]\n"
          "  [--pjrt_platform tpu|cpu] [--toy_hidden H] [--toy_vocab V]\n"
          "  [--selftest]\n"
          "Endpoints: /healthz /readyz /metrics /v1/signature /v1/infer\n"
          "  /v1/decode /v1/reload /v1/rows (docs/serving.md). SIGTERM\n"
          "  drains gracefully; SIGHUP hot-swaps parameters from "
          "--bundle.\n"
          "Chaos: PTPU_SERVING_FAULTS=\"point@at[xcount][:ms];...\" with\n"
          "  points tick.slow backend.error reload.torn batch.window\n"
          "  rows.slow\n");
      return 0;
    } else {
      fprintf(stderr, "unknown flag %s (try --help)\n", a.c_str());
      return 2;
    }
  }
  g_faults.parse(getenv("PTPU_SERVING_FAULTS"));
  signal(SIGPIPE, SIG_IGN);
  if (do_selftest) return selftest(d);
  if (d.backend == "toy") {
    d.sched.backend.reset(
        new ToyBackend(d.slots, d.toy_hidden, d.toy_vocab,
                                         d.toy_tick_us));
  } else {
    if (d.bundle_specs.empty()) {
      fprintf(stderr, "--bundle is required (or --backend toy)\n");
      return 2;
    }
    std::string err;
    if (!d.load_bundle(&err)) {
      fprintf(stderr, "paddle_tpu_serving: %s\n", err.c_str());
      return 1;
    }
    // real-model decode over the bundle (pjrt backend): continuous
    // per-tick step decode when the bundle exported step modules,
    // else the drain-batch whole-loop fallback with the recorded
    // skip reason already logged by load_bundle_state
    if (d.backend == "pjrt") {
      auto bs = d.cur_bundle();
      if (bs->step_init_prog >= 0 && bs->step_step_prog >= 0) {
        auto* sb = new StepBundleBackend(bs);
        d.sched.backend.reset(sb);
        d.slots = sb->slots();   // the exported slot batch IS the array
        d.bundle_decode = true;
        fprintf(stderr,
                "decode: continuous per-tick step decode, %d slots "
                "(beam %d, max_length %d)\n",
                sb->slots(), bs->step_beam, bs->step_max_len);
      } else if (bs->has_decode) {
        auto wl = std::make_unique<WholeLoopBackend>(bs);
        if (wl->usable()) {
          d.slots = wl->slots();
          d.sched.backend = std::move(wl);
          d.bundle_decode = true;
          fprintf(stderr,
                  "decode: drain-batch whole-loop fallback, %d slots "
                  "(%s)\n",
                  d.slots,
                  bs->step_skip_reason.empty()
                      ? "bundle predates step export"
                      : bs->step_skip_reason.c_str());
        }
      }
    }
  }
  if (d.sched.backend) {
    d.sched.drain_mode = d.drain_batch;
    d.sched.max_queue = d.max_queue;
    d.sched.high_water = d.queue_high_water;
    d.sched.start();
  }
  g_metrics.set("paddle_serving_slots_total", double(d.slots),
                "configured decode slot count");
  g_metrics.set("paddle_serving_threads", double(d.threads),
                "HTTP worker threads (shared-parameter sessions)");
  std::string err;
  if (!d.start_listen(&err)) {
    fprintf(stderr, "paddle_tpu_serving: %s\n", err.c_str());
    return 1;
  }
  if (pipe(g_sig_pipe) != 0) {
    fprintf(stderr, "paddle_tpu_serving: signal pipe failed\n");
    return 1;
  }
  struct sigaction sa;
  memset(&sa, 0, sizeof(sa));
  sa.sa_handler = ptpu_serving_on_signal;
  // SA_RESTART: the handler only writes a pipe byte, and without it a
  // SIGHUP delivered to a worker blocked in recv() would EINTR the
  // read and drop that client's in-flight request mid-"zero-downtime"
  // reload (main's pipe read still returns: data arrives, not EINTR)
  sa.sa_flags = SA_RESTART;
  sigaction(SIGTERM, &sa, nullptr);
  sigaction(SIGINT, &sa, nullptr);
  sigaction(SIGHUP, &sa, nullptr);
  if (!d.start_http()) {
    fprintf(stderr, "paddle_tpu_serving: stop pipe failed\n");
    return 1;
  }
  printf("paddle_tpu_serving on port %d (backend=%s, slots=%d, %s)\n",
         d.port, d.backend.c_str(), d.slots,
         d.drain_batch ? "drain-batch" : "continuous-batching");
  fflush(stdout);   // the banner's "port N" is parsed: it goes FIRST
  if (!d.model_order.empty()) {
    fprintf(stderr, "models:");
    for (const auto& m : d.model_order) fprintf(stderr, " %s", m.c_str());
    if (d.batch_window_ms > 0)
      fprintf(stderr, " (infer micro-batching: window=%.1fms max=%d)",
              d.batch_window_ms, d.batch_max);
    fprintf(stderr, "\n");
  }
  std::thread srv([&d] { d.serve(); });
  // the signal event loop: SIGHUP reloads, SIGTERM/SIGINT fall through
  // to the graceful drain
  for (;;) {
    char c = 0;
    ssize_t n = read(g_sig_pipe[0], &c, 1);
    if (n < 0 && errno == EINTR) continue;
    if (n <= 0) break;
    if (c == 'h') {
      for (const auto& mname : d.model_order) {
        std::string msg;
        int code = d.do_reload(mname, d.cur_bundle_path(mname), &msg);
        fprintf(stderr, "SIGHUP reload [%s]: %d %s\n", mname.c_str(),
                code, msg.c_str());
      }
      fflush(stderr);
      continue;
    }
    break;  // 't': begin the drain
  }
  d.begin_drain();
  bool clean = d.wait_drained(d.drain_timeout_s);
  d.stop_accepting();
  srv.join();
  d.shutdown_ordered();
  for (int i = 0; i < 2; ++i)
    if (g_sig_pipe[i] >= 0) { close(g_sig_pipe[i]); g_sig_pipe[i] = -1; }
  fprintf(stderr, "paddle_tpu_serving: drained %s, exiting 0\n",
          clean ? "clean" : "past --drain_timeout_s (leftovers got 503)");
  return 0;
}
