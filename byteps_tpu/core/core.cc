// byteps_tpu native host core.
//
// TPU-native re-design of the reference worker core runtime
// (reference: byteps/common/{global.cc,operations.cc,scheduled_queue.cc,
// ready_table.cc}).  On TPU, the device data plane is XLA collectives, so the
// native layer keeps only what genuinely belongs on the host: the named-tensor
// registry with deterministic key assignment, tensor partitioning, key→server
// placement hashing, the priority ScheduledQueue with credit-based flow
// control, push-pull speed telemetry, and the Chrome-trace timeline recorder.
// Exposed as a flat C ABI consumed via ctypes (no pybind11 in this image).
//
// Deliberately ABSENT: the reference's ReadyTable (ready_table.{h,cc}).  Its
// job is rendezvous across the one-process-per-GPU layout — non-root local
// processes signal readiness over UDS and the root counts signals before
// driving NCCL/PUSH (reference: communicator.cc:164-207, global.cc:207-235).
// Here ONE process drives all local chips (in-jit mesh collectives replace
// the intra-host tier) so there are no local peers to count, and the PS
// plane's cross-worker rendezvous lives on the server (round tracking /
// barrier-by-generation in server.cc).  An earlier revision carried an
// unused port of it; it was removed rather than kept as dead surface.
//
// Thread-safety: every public entry point locks the owning object's mutex;
// objects are opaque handles created/destroyed by the caller.

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <unordered_map>
#include <utility>
#include <vector>

#define BPS_API extern "C" __attribute__((visibility("default")))

namespace {

int64_t now_us() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// ---------------------------------------------------------------------------
// Tensor registry: name -> declared key, assigned in declaration order so all
// workers agree without communication (reference: global.cc:427-451).  The
// registry survives suspend/resume; re-declaring an existing name returns the
// original key, which is what keeps keys stable across elastic restarts
// (reference: operations.cc:96-119).
// ---------------------------------------------------------------------------
struct Registry {
  std::mutex mu;
  std::unordered_map<std::string, int32_t> name2key;
  std::vector<std::string> names_in_order;
};

Registry g_registry;

}  // namespace

BPS_API int32_t bps_declare_tensor(const char* name) {
  std::lock_guard<std::mutex> lk(g_registry.mu);
  auto it = g_registry.name2key.find(name);
  if (it != g_registry.name2key.end()) return it->second;
  int32_t key = static_cast<int32_t>(g_registry.names_in_order.size());
  g_registry.name2key.emplace(name, key);
  g_registry.names_in_order.emplace_back(name);
  return key;
}

BPS_API int32_t bps_get_declared_key(const char* name) {
  std::lock_guard<std::mutex> lk(g_registry.mu);
  auto it = g_registry.name2key.find(name);
  return it == g_registry.name2key.end() ? -1 : it->second;
}

BPS_API int32_t bps_num_declared() {
  std::lock_guard<std::mutex> lk(g_registry.mu);
  return static_cast<int32_t>(g_registry.names_in_order.size());
}

// Copies the i-th declared name into buf (for resume re-declaration walks).
BPS_API int32_t bps_declared_name(int32_t idx, char* buf, int32_t buf_len) {
  std::lock_guard<std::mutex> lk(g_registry.mu);
  if (idx < 0 || idx >= (int32_t)g_registry.names_in_order.size()) return -1;
  const std::string& s = g_registry.names_in_order[idx];
  int32_t n = std::min<int32_t>(buf_len - 1, (int32_t)s.size());
  std::memcpy(buf, s.data(), n);
  buf[n] = '\0';
  return n;
}

BPS_API void bps_reset_registry() {
  std::lock_guard<std::mutex> lk(g_registry.mu);
  g_registry.name2key.clear();
  g_registry.names_in_order.clear();
}

// ---------------------------------------------------------------------------
// Key encoding + partitioning.
// The reference encodes partition i of declared tensor k as (k << 16) | i
// (reference: operations.cc:301-311) and splits tensors into page-aligned
// partitions of at most BYTEPS_PARTITION_BYTES (reference:
// operations.cc:140-180, global.cc:134-144).
// ---------------------------------------------------------------------------
BPS_API uint64_t bps_encode_key(int32_t declared_key, int32_t part_idx) {
  return (static_cast<uint64_t>(declared_key) << 16) |
         static_cast<uint64_t>(part_idx & 0xffff);
}

BPS_API int32_t bps_decode_declared_key(uint64_t key) {
  return static_cast<int32_t>(key >> 16);
}

BPS_API int32_t bps_decode_part_idx(uint64_t key) {
  return static_cast<int32_t>(key & 0xffff);
}

BPS_API int64_t bps_align(int64_t size, int64_t alignment) {
  return ((size + alignment - 1) / alignment) * alignment;
}

// Number of partitions for a tensor of `nbytes` with partition size
// `partition_bytes` (already page-aligned by the caller).
BPS_API int32_t bps_partition_count(int64_t nbytes, int64_t partition_bytes) {
  if (nbytes <= 0) return 1;
  return static_cast<int32_t>((nbytes + partition_bytes - 1) / partition_bytes);
}

// Fills offsets[i], lens[i] for each partition. Returns the count.
BPS_API int32_t bps_partition_bounds(int64_t nbytes, int64_t partition_bytes,
                                     int64_t* offsets, int64_t* lens) {
  int32_t n = bps_partition_count(nbytes, partition_bytes);
  int64_t off = 0;
  for (int32_t i = 0; i < n; ++i) {
    int64_t len = std::min(partition_bytes, nbytes - off);
    offsets[i] = off;
    lens[i] = len;
    off += len;
  }
  return n;
}

// ---------------------------------------------------------------------------
// Key -> server placement hashing (reference: global.cc:581-692 — naive,
// built_in, djb2, sdbm, mixed).  Used by the PS-parity tier to spread
// partitions over server shards, and by tests to pin down determinism.
// ---------------------------------------------------------------------------
namespace {
uint64_t hash_djb2(uint64_t k) {
  // djb2 over the decimal digits of the key, like the reference hashes the
  // stringified key.
  char buf[24];
  int n = std::snprintf(buf, sizeof(buf), "%llu", (unsigned long long)k);
  uint64_t h = 5381;
  for (int i = 0; i < n; ++i) h = ((h << 5) + h) + buf[i];
  return h;
}
uint64_t hash_sdbm(uint64_t k) {
  char buf[24];
  int n = std::snprintf(buf, sizeof(buf), "%llu", (unsigned long long)k);
  uint64_t h = 0;
  for (int i = 0; i < n; ++i) h = buf[i] + (h << 6) + (h << 16) - h;
  return h;
}
}  // namespace

BPS_API int32_t bps_key_to_server(uint64_t key, int32_t num_servers,
                                  const char* hash_fn) {
  if (num_servers <= 0) return 0;
  uint64_t h;
  if (std::strcmp(hash_fn, "naive") == 0) {
    h = key;
  } else if (std::strcmp(hash_fn, "sdbm") == 0) {
    h = hash_sdbm(key);
  } else if (std::strcmp(hash_fn, "mixed") == 0) {
    h = hash_djb2(key) ^ hash_sdbm(key);
  } else {  // djb2 (default) and built_in both map here
    h = hash_djb2(key);
  }
  return static_cast<int32_t>(h % static_cast<uint64_t>(num_servers));
}

// ---------------------------------------------------------------------------
// Priority ScheduledQueue (reference: scheduled_queue.{h,cc}).
// Tasks are ordered by (priority desc, key asc); getTask() additionally
// enforces a credit budget of bytes in flight when enabled (reference:
// scheduled_queue.cc:26-46,82-102,136-139,197-203).  Unlike the reference we
// keep a heap-free sorted insert into a deque: queues are short (hundreds of
// buckets) and the host side is not the bottleneck on TPU.
// ---------------------------------------------------------------------------
namespace {
struct QTask {
  uint64_t key;
  int32_t priority;
  int64_t nbytes;
};

struct ScheduledQueue {
  std::mutex mu;
  std::deque<QTask> tasks;
  bool credit_enabled;
  int64_t credit;  // bytes allowed in flight
  std::atomic<int64_t> pending{0};
};
}  // namespace

BPS_API void* bps_queue_create(int32_t credit_enabled, int64_t credit_bytes) {
  auto* q = new ScheduledQueue();
  q->credit_enabled = credit_enabled != 0;
  q->credit = credit_bytes;
  return q;
}

BPS_API void bps_queue_destroy(void* qp) {
  delete static_cast<ScheduledQueue*>(qp);
}

BPS_API void bps_queue_add(void* qp, uint64_t key, int32_t priority,
                           int64_t nbytes) {
  auto* q = static_cast<ScheduledQueue*>(qp);
  std::lock_guard<std::mutex> lk(q->mu);
  QTask t{key, priority, nbytes};
  // Sorted insert: higher priority first; ties broken by smaller key
  // (reference: scheduled_queue.cc:82-102).
  auto it = std::upper_bound(
      q->tasks.begin(), q->tasks.end(), t, [](const QTask& a, const QTask& b) {
        if (a.priority != b.priority) return a.priority > b.priority;
        return a.key < b.key;
      });
  q->tasks.insert(it, t);
  q->pending.fetch_add(1);
}

// Pops the highest-priority task whose size fits in the remaining credit.
// Returns nbytes and writes the key, or -1 if nothing is eligible.
BPS_API int64_t bps_queue_get(void* qp, uint64_t* out_key,
                              int32_t* out_priority) {
  auto* q = static_cast<ScheduledQueue*>(qp);
  std::lock_guard<std::mutex> lk(q->mu);
  for (auto it = q->tasks.begin(); it != q->tasks.end(); ++it) {
    if (q->credit_enabled && it->nbytes > q->credit) continue;
    QTask t = *it;
    q->tasks.erase(it);
    if (q->credit_enabled) q->credit -= t.nbytes;
    q->pending.fetch_sub(1);
    *out_key = t.key;
    if (out_priority) *out_priority = t.priority;
    return t.nbytes;
  }
  return -1;
}

// Pops the task with a specific key (signal-directed dequeue, reference:
// scheduled_queue.cc:165-190).  Applies the same credit-eligibility check
// as bps_queue_get: a task larger than the remaining credit stays queued
// and -1 is returned — subtracting unconditionally would drive the credit
// negative and stall bps_queue_get until enough finishes were reported.
BPS_API int64_t bps_queue_get_key(void* qp, uint64_t key) {
  auto* q = static_cast<ScheduledQueue*>(qp);
  std::lock_guard<std::mutex> lk(q->mu);
  for (auto it = q->tasks.begin(); it != q->tasks.end(); ++it) {
    if (it->key == key) {
      if (q->credit_enabled && it->nbytes > q->credit) return -1;
      int64_t n = it->nbytes;
      if (q->credit_enabled) q->credit -= n;
      q->tasks.erase(it);
      q->pending.fetch_sub(1);
      return n;
    }
  }
  return -1;
}

BPS_API void bps_queue_report_finish(void* qp, int64_t nbytes) {
  auto* q = static_cast<ScheduledQueue*>(qp);
  std::lock_guard<std::mutex> lk(q->mu);
  if (q->credit_enabled) q->credit += nbytes;
}

BPS_API int64_t bps_queue_pending(void* qp) {
  return static_cast<ScheduledQueue*>(qp)->pending.load();
}

// ---------------------------------------------------------------------------
// Push-pull speed telemetry (reference: global.cc:712-767): ring buffer of
// (timestamp, bytes) push events; speed is a moving average over the last
// `window_us` (reference uses 10 s).
// ---------------------------------------------------------------------------
namespace {
struct Telemetry {
  std::mutex mu;
  std::deque<std::pair<int64_t, int64_t>> events;  // (us, bytes)
  int64_t window_us = 10 * 1000 * 1000;
};

Telemetry g_telemetry;
}  // namespace

BPS_API void bps_telemetry_set_window_us(int64_t window_us) {
  std::lock_guard<std::mutex> lk(g_telemetry.mu);
  g_telemetry.window_us = window_us;
}

BPS_API void bps_telemetry_record(int64_t bytes) {
  std::lock_guard<std::mutex> lk(g_telemetry.mu);
  int64_t t = now_us();
  g_telemetry.events.emplace_back(t, bytes);
  while (!g_telemetry.events.empty() &&
         g_telemetry.events.front().first < t - g_telemetry.window_us) {
    g_telemetry.events.pop_front();
  }
}

// Moving-average push throughput in MB/s over the telemetry window.
BPS_API double bps_telemetry_speed_mbps() {
  std::lock_guard<std::mutex> lk(g_telemetry.mu);
  int64_t t = now_us();
  int64_t total = 0;
  for (auto& e : g_telemetry.events) {
    if (e.first >= t - g_telemetry.window_us) total += e.second;
  }
  double secs = g_telemetry.window_us / 1e6;
  return (total / 1e6) / secs;
}

BPS_API void bps_telemetry_reset() {
  std::lock_guard<std::mutex> lk(g_telemetry.mu);
  g_telemetry.events.clear();
}

// ---------------------------------------------------------------------------
// Chrome-trace timeline recorder (reference: global.cc:463-579, format in
// docs/timeline.md).  Complete events ("ph":"X") with (name, stage, ts, dur,
// tid=stage-id) accumulated in memory and dumped to <dir>/<rank>/comm.json.
// ---------------------------------------------------------------------------
namespace {
struct TraceEvent {
  std::string name;
  std::string stage;
  int64_t ts_us;
  int64_t dur_us;
  // Per-partition detail (reference closes one span per partition per
  // pipeline stage, global.cc:463-579).  key < 0 means "not a partition
  // event" and the args object is omitted from the dump.
  int64_t key = -1;
  int64_t bytes = 0;
  int32_t priority = 0;
  // Named integer args of a main-thread stage span (ROUND, D2H, ...:
  // common/stage_spans.py).  When present they ARE the args object.
  std::vector<std::pair<std::string, int64_t>> extra;
};

struct Tracer {
  std::mutex mu;
  std::vector<TraceEvent> events;
  bool on = false;
};

Tracer g_tracer;
}  // namespace

BPS_API void bps_trace_enable(int32_t on) {
  std::lock_guard<std::mutex> lk(g_tracer.mu);
  g_tracer.on = on != 0;
}

BPS_API int64_t bps_trace_now_us() { return now_us(); }

BPS_API void bps_trace_record(const char* name, const char* stage,
                              int64_t ts_us, int64_t dur_us) {
  std::lock_guard<std::mutex> lk(g_tracer.mu);
  if (!g_tracer.on) return;
  g_tracer.events.push_back(TraceEvent{name, stage, ts_us, dur_us});
}

// Per-partition span: one row per partition per stage (QUEUE/PUSH/PULL on
// the PS plane), carrying the partition key, wire bytes, and priority as
// Chrome-trace args.
BPS_API void bps_trace_record_part(const char* name, const char* stage,
                                   int64_t ts_us, int64_t dur_us,
                                   int64_t key, int64_t bytes,
                                   int32_t priority) {
  std::lock_guard<std::mutex> lk(g_tracer.mu);
  if (!g_tracer.on) return;
  g_tracer.events.push_back(
      TraceEvent{name, stage, ts_us, dur_us, key, bytes, priority});
}

// Span with `n` named integer args: `keys` is their comma-separated names,
// `vals` their values in the same order.  Consecutive args whose name ends
// in "[]" and is the same are one list in the dump, under the name
// without it.
BPS_API void bps_trace_record_args(const char* name, const char* stage,
                                   int64_t ts_us, int64_t dur_us,
                                   const char* keys, const int64_t* vals,
                                   int32_t n) {
  TraceEvent ev{name, stage, ts_us, dur_us};
  const char* k = keys;
  for (int32_t i = 0; i < n; ++i) {
    const char* end = std::strchr(k, ',');
    std::string key = end ? std::string(k, end - k) : std::string(k);
    ev.extra.emplace_back(std::move(key), vals[i]);
    if (!end) break;
    k = end + 1;
  }
  std::lock_guard<std::mutex> lk(g_tracer.mu);
  if (!g_tracer.on) return;
  g_tracer.events.push_back(std::move(ev));
}

BPS_API int64_t bps_trace_count() {
  std::lock_guard<std::mutex> lk(g_tracer.mu);
  return (int64_t)g_tracer.events.size();
}

namespace {
// Minimal JSON string escaping (quotes, backslashes, control chars).
std::string json_escape(const std::string& s) {
  std::string out;
  out.reserve(s.size());
  for (unsigned char c : s) {
    switch (c) {
      case '"': out += "\\\""; break;
      case '\\': out += "\\\\"; break;
      case '\n': out += "\\n"; break;
      case '\r': out += "\\r"; break;
      case '\t': out += "\\t"; break;
      default:
        if (c < 0x20) {
          char buf[8];
          std::snprintf(buf, sizeof(buf), "\\u%04x", c);
          out += buf;
        } else {
          out += (char)c;
        }
    }
  }
  return out;
}
}  // namespace

// Dumps accumulated events as a Chrome trace (JSON array of complete events,
// one pid per rank) and clears the buffer. Returns 0 on success.
BPS_API int32_t bps_trace_dump(const char* path, int32_t rank) {
  std::lock_guard<std::mutex> lk(g_tracer.mu);
  FILE* f = std::fopen(path, "w");
  if (!f) return -1;
  std::fputs("{\"traceEvents\":[\n", f);
  bool first = true;
  for (auto& e : g_tracer.events) {
    if (!first) std::fputs(",\n", f);
    first = false;
    std::fprintf(f,
                 "{\"name\":\"%s\",\"cat\":\"comm\",\"ph\":\"X\",\"ts\":%lld,"
                 "\"dur\":%lld,\"pid\":%d,\"tid\":\"%s\"",
                 json_escape(e.name).c_str(), (long long)e.ts_us,
                 (long long)e.dur_us, rank, json_escape(e.stage).c_str());
    if (!e.extra.empty()) {
      std::fputs(",\"args\":{", f);
      for (size_t i = 0; i < e.extra.size(); ++i) {
        const std::string& key = e.extra[i].first;
        const size_t len = key.size();
        if (len < 2 || key.compare(len - 2, 2, "[]") != 0) {
          std::fprintf(f, "%s\"%s\":%lld", i ? "," : "",
                       json_escape(key).c_str(),
                       (long long)e.extra[i].second);
          continue;
        }
        std::fprintf(f, "%s\"%s\":[%lld", i ? "," : "",
                     json_escape(key.substr(0, len - 2)).c_str(),
                     (long long)e.extra[i].second);
        while (i + 1 < e.extra.size() && e.extra[i + 1].first == key) {
          std::fprintf(f, ",%lld", (long long)e.extra[++i].second);
        }
        std::fputs("]", f);
      }
      std::fputs("}", f);
    } else if (e.key >= 0) {
      std::fprintf(f,
                   ",\"args\":{\"key\":%lld,\"bytes\":%lld,\"priority\":%d}",
                   (long long)e.key, (long long)e.bytes, e.priority);
    }
    std::fputs("}", f);
  }
  std::fputs("\n],\"displayTimeUnit\":\"ms\"}\n", f);
  std::fclose(f);
  g_tracer.events.clear();
  return 0;
}

// ---------------------------------------------------------------------------
// Handle manager (reference: torch/handle_manager.{h,cc}): int handle ->
// completion status for the eager async API.
// ---------------------------------------------------------------------------
namespace {
struct HandleManager {
  std::mutex mu;
  int32_t next = 0;
  std::unordered_map<int32_t, int32_t> done;  // handle -> 1 when complete
};

HandleManager g_handles;
}  // namespace

BPS_API int32_t bps_handle_allocate() {
  std::lock_guard<std::mutex> lk(g_handles.mu);
  int32_t h = g_handles.next++;
  g_handles.done[h] = 0;
  return h;
}

BPS_API void bps_handle_mark_done(int32_t h) {
  std::lock_guard<std::mutex> lk(g_handles.mu);
  g_handles.done[h] = 1;
}

BPS_API int32_t bps_handle_poll(int32_t h) {
  std::lock_guard<std::mutex> lk(g_handles.mu);
  auto it = g_handles.done.find(h);
  return it == g_handles.done.end() ? -1 : it->second;
}

BPS_API void bps_handle_release(int32_t h) {
  std::lock_guard<std::mutex> lk(g_handles.mu);
  g_handles.done.erase(h);
}
