// vtpu_ingest — native high-rate DogStatsD ingest bridge.
//
// The TPU-native analogue of veneur's ingest front half
// (server.go sym: Server.ReadMetricSocket, Server.HandleMetricPacket;
// samplers/parser.go sym: ParseMetric; worker.go sym: Worker.ProcessMetric's
// dispatch-by-digest): SO_REUSEPORT UDP reader threads, a byte-level
// DogStatsD parser, a sharded MetricKey-interning hash table assigning
// device bank slots, and per-bank sample rings that the Python pump drains
// into fixed-shape batches for the XLA scatter kernels.
//
// Conformance contract: for every line this parser accepts, the produced
// (name, type, joined_tags, digest, value, rate, scope) must be
// bit-identical with veneur_tpu/ingest/parser.py. Lines it cannot prove
// bit-identical handling for (events, service checks, invalid UTF-8,
// numeric tokens with '_' or whitespace that CPython's float() would
// accept) are routed to the "other" queue for the Python slow path
// instead of being guessed at.
//
// Exposed as a C ABI consumed via ctypes (no pybind11 in this image).

#include <arpa/inet.h>
#include <netinet/in.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/types.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

// ---------------------------------------------------------------- constants

enum Bank : int { B_HISTO = 0, B_COUNTER = 1, B_GAUGE = 2, B_SET = 3 };
constexpr int NUM_BANKS = 4;

enum MType : uint8_t {
  MT_COUNTER = 0,
  MT_GAUGE = 1,
  MT_TIMER = 2,
  MT_HISTOGRAM = 3,
  MT_SET = 4,
};

// Scope values match ingest/parser.py MIXED_SCOPE / LOCAL_ONLY / GLOBAL_ONLY.
enum Scope : uint8_t { SC_MIXED = 0, SC_LOCAL = 1, SC_GLOBAL = 2 };

constexpr int NUM_SHARDS = 16;

const char* const MTYPE_NAMES[5] = {"counter", "gauge", "timer", "histogram",
                                    "set"};

inline int bank_of(MType t) {
  switch (t) {
    case MT_COUNTER: return B_COUNTER;
    case MT_GAUGE: return B_GAUGE;
    case MT_TIMER:
    case MT_HISTOGRAM: return B_HISTO;
    case MT_SET: return B_SET;
  }
  return B_HISTO;
}

// ---------------------------------------------------------------- hashing
// FNV-1a, identical to utils/hashing.py (itself parity with the fnv32a in
// samplers/parser.go) so proxies/tests agree about key identity.

constexpr uint32_t FNV32_OFFSET = 0x811C9DC5u;
constexpr uint32_t FNV32_PRIME = 0x01000193u;
constexpr uint64_t FNV64_OFFSET = 0xCBF29CE484222325ull;
constexpr uint64_t FNV64_PRIME = 0x00000100000001B3ull;

inline uint32_t fnv1a_32(const uint8_t* p, size_t n, uint32_t h) {
  for (size_t i = 0; i < n; i++) h = (h ^ p[i]) * FNV32_PRIME;
  return h;
}

inline uint64_t fnv1a_64(const uint8_t* p, size_t n, uint64_t h) {
  for (size_t i = 0; i < n; i++) h = (h ^ p[i]) * FNV64_PRIME;
  return h;
}

inline uint64_t fmix64(uint64_t h) {  // murmur3 finalizer (hashing.py fmix64)
  h ^= h >> 33;
  h *= 0xFF51AFD7ED558CCDull;
  h ^= h >> 33;
  h *= 0xC4CEB9FE1A85EC53ull;
  h ^= h >> 33;
  return h;
}

// fnv1a over name + type-name + joined tags — the one definition of
// metric identity, shared by the statsd parse tail, the SSF sample
// path, and the indicator timer (parity: utils/hashing.py
// metric_digest)
inline uint32_t metric_digest32(const uint8_t* name, size_t name_len,
                                int mtype,
                                const std::string& joined_tags) {
  const char* tn = MTYPE_NAMES[mtype];
  uint32_t h = fnv1a_32(name, name_len, FNV32_OFFSET);
  h = fnv1a_32(reinterpret_cast<const uint8_t*>(tn), strlen(tn), h);
  h = fnv1a_32(reinterpret_cast<const uint8_t*>(joined_tags.data()),
               joined_tags.size(), h);
  return h;
}

// ---------------------------------------------------------------- utf8
// Strict UTF-8 validation: CPython's decoder only leaves bytes unchanged
// (decode('utf-8','replace') then re-encode) when the input is strictly
// valid, so "strictly valid" is exactly the fast-path condition.

bool utf8_valid(const uint8_t* s, size_t n) {
  size_t i = 0;
  while (i < n) {
    uint8_t b = s[i];
    if (b < 0x80) {
      i++;
    } else if ((b >> 5) == 0x6) {  // 110xxxxx
      if (b < 0xC2 || i + 1 >= n || (s[i + 1] & 0xC0) != 0x80) return false;
      i += 2;
    } else if ((b >> 4) == 0xE) {  // 1110xxxx
      if (i + 2 >= n) return false;
      uint8_t b1 = s[i + 1], b2 = s[i + 2];
      if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80) return false;
      if (b == 0xE0 && b1 < 0xA0) return false;        // overlong
      if (b == 0xED && b1 > 0x9F) return false;        // surrogates
      i += 3;
    } else if ((b >> 3) == 0x1E) {  // 11110xxx
      if (b > 0xF4 || i + 3 >= n) return false;
      uint8_t b1 = s[i + 1], b2 = s[i + 2], b3 = s[i + 3];
      if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80 ||
          (b3 & 0xC0) != 0x80)
        return false;
      if (b == 0xF0 && b1 < 0x90) return false;        // overlong
      if (b == 0xF4 && b1 > 0x8F) return false;        // > U+10FFFF
      i += 4;
    } else {
      return false;
    }
  }
  return true;
}

// ---------------------------------------------------------------- numbers
// CPython float() compatibility triage for a numeric token:
//   OK     — strtod agrees with float() (charset-restricted decimal forms)
//   ERROR  — float() would raise (both sides reject)
//   SLOW   — float() may accept forms strtod can't ('_' digit grouping,
//            exotic whitespace trimming) → route the line to Python.

enum NumVerdict { NUM_OK = 0, NUM_ERROR = 1, NUM_SLOW = 2 };

NumVerdict parse_pyfloat(const uint8_t* p, size_t n, double* out) {
  if (n == 0) return NUM_ERROR;
  for (size_t i = 0; i < n; i++) {
    uint8_t c = p[i];
    if ((c >= '0' && c <= '9') || c == '+' || c == '-' || c == '.' ||
        c == 'e' || c == 'E')
      continue;
    if (c == '_' || c == ' ' || (c >= 0x09 && c <= 0x0D)) return NUM_SLOW;
    return NUM_ERROR;  // 'x', 'p', letters, NUL, UTF-8 ws… float() raises too
  }
  char buf[64];
  if (n >= sizeof(buf)) return NUM_SLOW;  // absurd token; let Python decide
  memcpy(buf, p, n);
  buf[n] = '\0';
  char* end = nullptr;
  errno = 0;
  double v = strtod(buf, &end);
  if (end != buf + n) return NUM_ERROR;  // e.g. "1e", "--1", "."
  *out = v;                              // may be ±inf on overflow, like float()
  return NUM_OK;
}

// ---------------------------------------------------------------- parser

enum ParseVerdict {
  P_METRIC = 0,   // parsed a metric sample
  P_ERROR = 1,    // ParseError on both implementations
  P_OTHER = 2,    // event / service check / slow path → Python
};

struct ParsedMetric {
  MType mtype;
  uint8_t scope;
  double value;        // numeric types
  double rate;
  uint32_t digest;
  std::string name;        // raw bytes (validated UTF-8)
  std::string joined_tags; // sorted, comma-joined
  std::string member;      // set member bytes
};

// Parse one line. `scratch` vectors are caller-provided to avoid per-line
// allocation on the hot path.
ParseVerdict parse_line(
    const uint8_t* data, size_t len, ParsedMetric* m,
    std::vector<std::pair<const uint8_t*, size_t>>* secs,
    std::vector<std::pair<const uint8_t*, size_t>>* tags,
    const std::vector<std::string>* exclude = nullptr) {
  if (len == 0) return P_ERROR;
  if (len >= 3 && memcmp(data, "_e{", 3) == 0) return P_OTHER;
  if (len >= 4 && memcmp(data, "_sc|", 4) == 0) return P_OTHER;
  if (!utf8_valid(data, len)) return P_OTHER;  // replace-decode divergence

  const uint8_t* colon =
      static_cast<const uint8_t*>(memchr(data, ':', len));
  if (colon == nullptr || colon == data) return P_ERROR;
  const uint8_t* name = data;
  size_t name_len = static_cast<size_t>(colon - data);
  const uint8_t* rest = colon + 1;
  size_t rest_len = len - name_len - 1;

  // split rest on '|'
  secs->clear();
  {
    const uint8_t* p = rest;
    size_t remain = rest_len;
    for (;;) {
      const uint8_t* bar =
          static_cast<const uint8_t*>(memchr(p, '|', remain));
      if (bar == nullptr) {
        secs->emplace_back(p, remain);
        break;
      }
      secs->emplace_back(p, static_cast<size_t>(bar - p));
      remain -= static_cast<size_t>(bar - p) + 1;
      p = bar + 1;
    }
  }
  if (secs->size() < 2) return P_ERROR;  // missing type

  const uint8_t* valstr = (*secs)[0].first;
  size_t val_len = (*secs)[0].second;
  const uint8_t* typestr = (*secs)[1].first;
  size_t type_len = (*secs)[1].second;

  MType mtype;
  bool is_dist = false;
  if (type_len == 1) {
    switch (typestr[0]) {
      case 'c': mtype = MT_COUNTER; break;
      case 'g': mtype = MT_GAUGE; break;
      case 'h': mtype = MT_HISTOGRAM; break;
      case 's': mtype = MT_SET; break;
      case 'd': mtype = MT_HISTOGRAM; is_dist = true; break;
      default: return P_ERROR;
    }
  } else if (type_len == 2 && typestr[0] == 'm' && typestr[1] == 's') {
    mtype = MT_TIMER;
  } else {
    return P_ERROR;
  }

  double value = 0.0;
  if (mtype == MT_SET) {
    m->member.assign(reinterpret_cast<const char*>(valstr), val_len);
  } else {
    if (val_len == 0) return P_ERROR;
    NumVerdict nv = parse_pyfloat(valstr, val_len, &value);
    if (nv == NUM_SLOW) return P_OTHER;
    if (nv == NUM_ERROR) return P_ERROR;
    if (!std::isfinite(value)) return P_ERROR;
  }

  double rate = 1.0;
  uint8_t scope = is_dist ? SC_GLOBAL : SC_MIXED;
  bool seen_rate = false, seen_tags = false;
  tags->clear();

  for (size_t si = 2; si < secs->size(); si++) {
    const uint8_t* sec = (*secs)[si].first;
    size_t sec_len = (*secs)[si].second;
    if (sec_len == 0) return P_ERROR;
    if (sec[0] == '@') {
      if (seen_rate) return P_ERROR;
      seen_rate = true;
      NumVerdict nv = parse_pyfloat(sec + 1, sec_len - 1, &rate);
      if (nv == NUM_SLOW) return P_OTHER;
      if (nv == NUM_ERROR) return P_ERROR;
      if (!(rate > 0.0 && rate <= 1.0)) return P_ERROR;
      if ((mtype == MT_GAUGE || mtype == MT_SET) && rate != 1.0)
        return P_ERROR;
    } else if (sec[0] == '#') {
      if (seen_tags) return P_ERROR;
      seen_tags = true;
      const uint8_t* p = sec + 1;
      size_t remain = sec_len - 1;
      for (;;) {
        const uint8_t* comma =
            remain ? static_cast<const uint8_t*>(memchr(p, ',', remain))
                   : nullptr;
        size_t tlen = comma ? static_cast<size_t>(comma - p) : remain;
        if (tlen == 15 && memcmp(p, "veneurlocalonly", 15) == 0) {
          scope = SC_LOCAL;
        } else if (tlen == 16 && memcmp(p, "veneurglobalonly", 16) == 0) {
          scope = SC_GLOBAL;
        } else if (tlen > 0) {
          tags->emplace_back(p, tlen);
        }
        if (!comma) break;
        remain -= tlen + 1;
        p = comma + 1;
      }
      if (exclude && !exclude->empty()) {
        // tags_exclude semantics (config.go): drop tags whose NAME
        // (before ':', or the whole tag) matches, BEFORE the key is
        // built, so excluded-tag variants aggregate together
        tags->erase(
            std::remove_if(
                tags->begin(), tags->end(),
                [&](const std::pair<const uint8_t*, size_t>& t) {
                  const uint8_t* colon = static_cast<const uint8_t*>(
                      memchr(t.first, ':', t.second));
                  size_t nlen = colon
                      ? static_cast<size_t>(colon - t.first) : t.second;
                  for (const std::string& ex : *exclude)
                    if (ex.size() == nlen &&
                        memcmp(ex.data(), t.first, nlen) == 0)
                      return true;
                  return false;
                }),
            tags->end());
      }
      // byte-wise sort == code-point sort for valid UTF-8
      std::sort(tags->begin(), tags->end(),
                [](const std::pair<const uint8_t*, size_t>& a,
                   const std::pair<const uint8_t*, size_t>& b) {
                  int c = memcmp(a.first, b.first,
                                 a.second < b.second ? a.second : b.second);
                  if (c != 0) return c < 0;
                  return a.second < b.second;
                });
    } else {
      return P_ERROR;
    }
  }

  if (name_len == 0) return P_ERROR;

  m->mtype = mtype;
  m->scope = scope;
  m->value = value;
  m->rate = rate;
  m->name.assign(reinterpret_cast<const char*>(name), name_len);
  m->joined_tags.clear();
  for (size_t i = 0; i < tags->size(); i++) {
    if (i) m->joined_tags.push_back(',');
    m->joined_tags.append(reinterpret_cast<const char*>((*tags)[i].first),
                          (*tags)[i].second);
  }

  m->digest = metric_digest32(name, name_len, mtype, m->joined_tags);
  return P_METRIC;
}

// ---------------------------------------------------------------- rings

struct Ring {
  std::mutex mu;
  std::vector<int32_t> slots;
  std::vector<float> a;
  std::vector<float> b;
  std::vector<int32_t> c;
  size_t cap = 0, head = 0, count = 0;
  // the most samples the ring has held since take_high last reset it
  size_t high = 0;
  uint64_t drops = 0;

  void init(size_t capacity) {
    cap = capacity;
    slots.resize(cap);
    a.resize(cap);
    b.resize(cap);
    c.resize(cap);
  }

  // bulk append; drops (and counts) what doesn't fit — veneur's
  // full-worker-channel backpressure drop, not blocking.
  void push(const int32_t* s, const float* av, const float* bv,
            const int32_t* cv, size_t n) {
    std::lock_guard<std::mutex> g(mu);
    size_t space = cap - count;
    if (n > space) {
      drops += n - space;
      n = space;
    }
    size_t tail = (head + count) % cap;
    size_t first = std::min(n, cap - tail);
    memcpy(&slots[tail], s, first * sizeof(int32_t));
    memcpy(&a[tail], av, first * sizeof(float));
    memcpy(&b[tail], bv, first * sizeof(float));
    memcpy(&c[tail], cv, first * sizeof(int32_t));
    if (n > first) {
      memcpy(&slots[0], s + first, (n - first) * sizeof(int32_t));
      memcpy(&a[0], av + first, (n - first) * sizeof(float));
      memcpy(&b[0], bv + first, (n - first) * sizeof(float));
      memcpy(&c[0], cv + first, (n - first) * sizeof(int32_t));
    }
    count += n;
    if (count > high) high = count;
  }

  // the high water since the last take, which starts again from what
  // the ring holds now
  size_t take_high() {
    std::lock_guard<std::mutex> g(mu);
    size_t h = high;
    high = count;
    return h;
  }

  size_t pop(int32_t* s, float* av, float* bv, int32_t* cv, size_t max_n) {
    std::lock_guard<std::mutex> g(mu);
    size_t n = std::min(count, max_n);
    size_t first = std::min(n, cap - head);
    memcpy(s, &slots[head], first * sizeof(int32_t));
    memcpy(av, &a[head], first * sizeof(float));
    memcpy(bv, &b[head], first * sizeof(float));
    memcpy(cv, &c[head], first * sizeof(int32_t));
    if (n > first) {
      memcpy(s + first, &slots[0], (n - first) * sizeof(int32_t));
      memcpy(av + first, &a[0], (n - first) * sizeof(float));
      memcpy(bv + first, &b[0], (n - first) * sizeof(float));
      memcpy(cv + first, &c[0], (n - first) * sizeof(int32_t));
    }
    head = (head + n) % cap;
    count -= n;
    return n;
  }
};

// ---------------------------------------------------------------- interner

inline uint64_t mono_ns() {
  return static_cast<uint64_t>(
      std::chrono::duration_cast<std::chrono::nanoseconds>(
          std::chrono::steady_clock::now().time_since_epoch())
          .count());
}

struct NewKey {
  uint8_t bank, mtype, scope;
  int32_t slot;
  std::string name, tags;
};

struct Shard {
  std::mutex mu;
  // key string: name '\x1f' type-name '\x1f' joined_tags
  std::unordered_map<std::string, int32_t> map[NUM_BANKS];
};

struct BankMeta {
  int32_t capacity = 0;
  std::vector<std::atomic<uint32_t>> last_interval;
  std::vector<std::atomic<uint8_t>> scope;
  std::mutex free_mu;
  std::vector<int32_t> free_slots;
  std::atomic<uint32_t> interval{0};
  std::atomic<uint64_t> drops_no_slot{0};
  std::atomic<int64_t> key_count{0};
  // running totals: keys minted into a slot, keys the idle TTL evicted
  std::atomic<uint64_t> keys_interned{0}, keys_evicted{0};

  void init(int32_t cap) {
    capacity = cap;
    last_interval = std::vector<std::atomic<uint32_t>>(cap);
    scope = std::vector<std::atomic<uint8_t>>(cap);
    for (int32_t i = 0; i < cap; i++) {
      last_interval[i].store(0, std::memory_order_relaxed);
      scope[i].store(0, std::memory_order_relaxed);
    }
    free_slots.reserve(cap);
    for (int32_t i = cap - 1; i >= 0; i--) free_slots.push_back(i);
  }
};

// ---------------------------------------------------------------- bridge

constexpr int RING_WAYS = 8;  // sub-rings per bank: writers shard by
                              // thread, so producers don't serialize
                              // against each other or the drain memcpy

// What one UDP reader thread did, running totals: datagrams received,
// lines parsed, ns from recvmmsg's return to its burst staged in the
// rings. With SO_REUSEPORT the kernel picks a flow's reader by the
// flow's hash: these say how the flows fell.
struct ReaderStat {
  std::atomic<uint64_t> packets{0}, lines{0}, busy_ns{0};
};
constexpr int MAX_READERS = 64;  // readers past it share the last entry

struct Bridge {
  BankMeta banks[NUM_BANKS];
  Shard shards[NUM_SHARDS];
  Ring rings[NUM_BANKS][RING_WAYS];
  int hll_precision = 14;
  int idle_ttl = 16;
  // bumped on every advance_interval (evictions may reassign slots);
  // thread-local key caches check it and self-invalidate
  std::atomic<uint64_t> intern_epoch{0};
  // process-unique identity: thread_local LocalStages outlive any one
  // Bridge, so their memos must be scoped to the bridge they were
  // filled from — and a raw pointer is not enough (a new Bridge can be
  // allocated at a freed one's address with a matching epoch)
  uint64_t instance_id = 0;

  std::mutex newkeys_mu;
  std::deque<NewKey> newkeys;

  // set ONCE before readers start (no synchronization on the hot path)
  std::vector<std::string> tags_exclude;
  // indicator-span duration timer name ("" = disabled); set before start
  std::string indicator_timer;
  std::atomic<uint64_t> ssf_spans{0}, ssf_fallbacks{0};

  std::mutex other_mu;
  std::deque<std::string> other;
  size_t other_cap = 65536;
  uint64_t other_drops = 0;

  // SSF datagrams the native fast path could not express (STATUS
  // samples): raw bytes for the Python span pipeline, plus the native
  // SSF listener's own counters/port
  std::mutex ssf_other_mu;
  std::deque<std::string> ssf_other;
  size_t ssf_other_cap = 65536;
  uint64_t ssf_other_drops = 0;
  std::atomic<uint64_t> ssf_errors{0};
  int ssf_bound_port = 0;
  int ssf_max_dgram = 16384;

  // framed SSF streams (unix://, tcp://): what every stream reader
  // tallies, added once a read. ssf_stream_wait_ns stays 0: a reader
  // that finds a sub-ring full drops and counts (ring_drops) as every
  // transport of the bridge does; it never waits for room
  std::atomic<uint64_t> ssf_stream_frames{0}, ssf_stream_conns{0},
      ssf_stream_conn_errors{0}, ssf_stream_read_ns{0},
      ssf_stream_wait_ns{0};
  // connection readers are detached; stop waits until the last has left
  std::mutex stream_mu;
  std::condition_variable stream_cv;
  int stream_active = 0;

  std::atomic<uint64_t> packets{0}, lines{0}, samples{0}, parse_errors{0},
      slow_routed{0};
  // The bridge-wide arrival order: every datagram (a read of a framed
  // stream, a packet handed in from Python) takes the next number when
  // it is received, BEFORE it is counted in `packets`, and its gauge
  // samples carry it in the ring's `c` column. A gauge is its last
  // write by this order, whichever reader staged it on whichever
  // sub-ring (ingest_gauge_batch lands a batch by it). 32 bits that
  // wrap: the engine compares stamps by their distance from the
  // interval's base.
  std::atomic<uint32_t> arrival{0};
  ReaderStat reader_stats[MAX_READERS];
  std::atomic<int> n_readers{0};
  // ns inside intern_key's slow path (a key that holds no slot: the free
  // list, the shard map's insert, the new-key record), all callers summed
  std::atomic<uint64_t> intern_ns{0};

  std::vector<int> socks;
  std::vector<std::thread> readers;
  std::atomic<bool> stop{false};
  int bound_port = 0;
  int max_packet = 8192;
};

// The next `n` numbers of the arrival order: datagram i of a burst is
// the returned number + i.
inline uint32_t arrive(Bridge* br, int n) {
  return br->arrival.fetch_add(static_cast<uint32_t>(n),
                               std::memory_order_relaxed) + 1;
}

// per-thread parse + staging state
struct LocalStage {
  std::vector<std::pair<const uint8_t*, size_t>> secs, tags;
  ParsedMetric m;
  std::string keybuf;
  // key -> slot memo, valid within one (bridge, intern epoch):
  // steady-state hot keys skip the sharded map (and its mutex)
  // entirely; a thread that served a different bridge self-invalidates
  std::unordered_map<std::string, int32_t> key_cache[NUM_BANKS];
  uint64_t cache_epoch = ~0ull;
  uint64_t cache_owner = 0;  // Bridge::instance_id the memo belongs to
  std::vector<int32_t> slots[NUM_BANKS];
  std::vector<float> a[NUM_BANKS];
  std::vector<float> b[NUM_BANKS];
  std::vector<int32_t> c[NUM_BANKS];

  int way = -1;
  // lines this stage has parsed (a reader adds its burst's to its
  // ReaderStat), and the arrival number of the datagram being parsed
  uint64_t lines = 0;
  uint32_t order = 0;

  void flush(Bridge* br) {
    if (way < 0) {
      static std::atomic<int> next_way{0};
      way = next_way.fetch_add(1, std::memory_order_relaxed) % RING_WAYS;
    }
    for (int bk = 0; bk < NUM_BANKS; bk++) {
      if (!slots[bk].empty()) {
        br->rings[bk][way].push(slots[bk].data(), a[bk].data(),
                                b[bk].data(), c[bk].data(),
                                slots[bk].size());
        slots[bk].clear();
        a[bk].clear();
        b[bk].clear();
        c[bk].clear();
      }
    }
  }
};

inline void touch_meta(BankMeta& bank, int32_t slot, uint8_t scope);

void build_key(const ParsedMetric& m, std::string* keybuf) {
  keybuf->clear();
  keybuf->append(m.name);
  keybuf->push_back('\x1f');
  keybuf->append(MTYPE_NAMES[m.mtype]);
  keybuf->push_back('\x1f');
  keybuf->append(m.joined_tags);
}

int32_t intern_key(Bridge* br, const ParsedMetric& m,
                   const std::string& keybuf) {
  int bk = bank_of(m.mtype);
  BankMeta& bank = br->banks[bk];
  Shard& sh = br->shards[m.digest & (NUM_SHARDS - 1)];

  int32_t slot;
  {
    std::lock_guard<std::mutex> g(sh.mu);
    auto it = sh.map[bk].find(keybuf);
    if (it != sh.map[bk].end()) {
      slot = it->second;
    } else {
      uint64_t t0 = mono_ns();
      {
        std::lock_guard<std::mutex> fg(bank.free_mu);
        if (bank.free_slots.empty()) {
          bank.drops_no_slot.fetch_add(1, std::memory_order_relaxed);
          return -1;
        }
        slot = bank.free_slots.back();
        bank.free_slots.pop_back();
      }
      sh.map[bk].emplace(keybuf, slot);
      bank.key_count.fetch_add(1, std::memory_order_relaxed);
      NewKey nk;
      nk.bank = static_cast<uint8_t>(bk);
      nk.mtype = static_cast<uint8_t>(m.mtype);
      nk.scope = m.scope;
      nk.slot = slot;
      nk.name = m.name;
      nk.tags = m.joined_tags;
      {
        std::lock_guard<std::mutex> ng(br->newkeys_mu);
        br->newkeys.push_back(std::move(nk));
      }
      bank.keys_interned.fetch_add(1, std::memory_order_relaxed);
      br->intern_ns.fetch_add(mono_ns() - t0, std::memory_order_relaxed);
    }
  }
  touch_meta(bank, slot, m.scope);
  return slot;
}

// Refresh per-slot liveness/scope. Read-mostly: unconditional stores on
// a hot slot ping-pong its cache line between reader cores; in steady
// state the values don't change, so check first and only write on
// difference.
inline void touch_meta(BankMeta& bank, int32_t slot, uint8_t scope) {
  uint32_t cur = bank.interval.load(std::memory_order_relaxed);
  if (bank.last_interval[slot].load(std::memory_order_relaxed) != cur)
    bank.last_interval[slot].store(cur, std::memory_order_relaxed);
  if (bank.scope[slot].load(std::memory_order_relaxed) != scope)
    bank.scope[slot].store(scope, std::memory_order_relaxed);
}

void route_other(Bridge* br, const uint8_t* line, size_t len) {
  std::lock_guard<std::mutex> g(br->other_mu);
  if (br->other.size() >= br->other_cap) {
    br->other_drops++;
    return;
  }
  br->other.emplace_back(reinterpret_cast<const char*>(line), len);
}

void stage_parsed(Bridge* br, LocalStage* st, const ParsedMetric& m);

void handle_line(Bridge* br, LocalStage* st, const uint8_t* line,
                 size_t len) {
  br->lines.fetch_add(1, std::memory_order_relaxed);
  st->lines++;
  ParseVerdict v = parse_line(
      line, len, &st->m, &st->secs, &st->tags,
      br->tags_exclude.empty() ? nullptr : &br->tags_exclude);
  if (v == P_ERROR) {
    br->parse_errors.fetch_add(1, std::memory_order_relaxed);
    return;
  }
  if (v == P_OTHER) {
    br->slow_routed.fetch_add(1, std::memory_order_relaxed);
    route_other(br, line, len);
    return;
  }
  stage_parsed(br, st, st->m);
}

// Intern + stage one parsed metric through the thread's LocalStage —
// the tail of handle_line, shared with the SSF span fast path.
void stage_parsed(Bridge* br, LocalStage* st, const ParsedMetric& m) {
  uint64_t ep = br->intern_epoch.load(std::memory_order_acquire);
  if (st->cache_epoch != ep || st->cache_owner != br->instance_id) {
    for (auto& c : st->key_cache) c.clear();
    st->cache_epoch = ep;
    st->cache_owner = br->instance_id;
  }
  int cbk = bank_of(m.mtype);
  build_key(m, &st->keybuf);
  int32_t slot;
  auto cit = st->key_cache[cbk].find(st->keybuf);
  if (cit != st->key_cache[cbk].end()) {
    slot = cit->second;
    touch_meta(br->banks[cbk], slot, m.scope);
  } else {
    slot = intern_key(br, m, st->keybuf);
    if (slot >= 0) st->key_cache[cbk].emplace(st->keybuf, slot);
  }
  if (slot < 0) return;
  int bk = bank_of(m.mtype);
  br->samples.fetch_add(1, std::memory_order_relaxed);
  switch (bk) {
    case B_HISTO:
    case B_COUNTER:
      st->slots[bk].push_back(slot);
      st->a[bk].push_back(static_cast<float>(m.value));
      st->b[bk].push_back(static_cast<float>(1.0 / m.rate));
      st->c[bk].push_back(0);
      break;
    case B_GAUGE:
      // last-write-wins: the sample carries its datagram's arrival
      // number. Ring order is arrival order within ONE stage only; the
      // engine turns the stamps into the interval's sequence numbers
      // at dispatch time (ingest_gauge_batch), under the same lock as
      // the flush swap. Every path that stages a gauge sets st->order
      // first (vlint NA05)
      st->slots[bk].push_back(slot);
      st->a[bk].push_back(static_cast<float>(m.value));
      st->b[bk].push_back(0.0f);
      st->c[bk].push_back(static_cast<int32_t>(st->order));
      break;
    case B_SET: {
      // member hash identical to hashing.py set_member_hash + the rho
      // computation in pipeline.py _process_locked
      int p = br->hll_precision;
      uint64_t h = fmix64(fnv1a_64(
          reinterpret_cast<const uint8_t*>(m.member.data()),
          m.member.size(), FNV64_OFFSET));
      uint32_t idx = static_cast<uint32_t>(h >> (64 - p));
      uint64_t rest = (h << p) | ((1ull << p) - 1);
      int rho = __builtin_clzll(rest) + 1;
      st->slots[bk].push_back(slot);
      st->a[bk].push_back(static_cast<float>(rho));
      st->b[bk].push_back(0.0f);
      st->c[bk].push_back(static_cast<int32_t>(idx));
      break;
    }
  }
}

void handle_buffer(Bridge* br, LocalStage* st, const uint8_t* data,
                   size_t len) {
  size_t i = 0;
  while (i < len) {
    const uint8_t* nl =
        static_cast<const uint8_t*>(memchr(data + i, '\n', len - i));
    size_t ll = nl ? static_cast<size_t>(nl - (data + i)) : len - i;
    if (ll > 0) handle_line(br, st, data + i, ll);
    i += ll + 1;
  }
}

// ---------------------------------------------------------------- ssf
// Native span->metrics fast path: decode one SSF datagram (the
// protobuf subset of ssf/protos/ssf.proto) and stage its embedded
// samples straight into the rings — the C++ twin of
// sinks/ssfmetrics.py (sample_to_metric + indicator_timer; parity:
// sinks/ssfmetrics/metrics.go sym: metricExtractionSink). Spans the
// fast path cannot express faithfully (STATUS samples, which become
// service checks in Python) make the WHOLE datagram fall back to the
// Python path — never a partial native landing.

// Unknown-field group nesting deeper than this makes the native parser
// hand the datagram to the Python fallback decoder instead of erroring:
// the Python protobuf runtime accepts deeper well-formed nesting, so
// rejecting here would be a parity divergence (round-5 advisory).
// MUST stay equal to ssf/framing.py PB_SKIP_MAX_DEPTH (vlint NA02).
constexpr int kPbSkipMaxDepth = 16;

struct PbReader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;
  bool deep = false;  // failed ONLY by exceeding kPbSkipMaxDepth

  uint64_t varint() {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 64) {
      uint8_t b = *p++;
      v |= static_cast<uint64_t>(b & 0x7F) << shift;
      if (!(b & 0x80)) return v;
      shift += 7;
    }
    ok = false;
    return 0;
  }

  bool tag(uint32_t* field, uint32_t* wt) {
    if (p >= end) return false;
    uint64_t t = varint();
    if (!ok) return false;
    *field = static_cast<uint32_t>(t >> 3);
    *wt = static_cast<uint32_t>(t & 7);
    // wire-format limits the decoders we must agree with enforce:
    // field numbers are 1..2^29-1 (0 and oversized tags are illegal)
    if (*field == 0 || (t >> 3) > 536870911ull) {
      ok = false;
      return false;
    }
    return true;
  }

  bool bytes(const uint8_t** s, size_t* n) {
    uint64_t len = varint();
    if (!ok || len > static_cast<uint64_t>(end - p)) {
      ok = false;
      return false;
    }
    *s = p;
    *n = static_cast<size_t>(len);
    p += len;
    return true;
  }

  float f32() {
    if (end - p < 4) {
      ok = false;
      return 0.0f;
    }
    float v;
    memcpy(&v, p, 4);
    p += 4;
    return v;
  }

  void skip(uint32_t field, uint32_t wt, int depth = 0) {
    switch (wt) {
      case 0: varint(); break;
      case 1: p = (end - p >= 8) ? p + 8 : (ok = false, end); break;
      case 2: {
        const uint8_t* s;
        size_t n;
        bytes(&s, &n);
        break;
      }
      case 3: {
        // START_GROUP in an unknown field: the decoders we must agree
        // with accept well-formed groups (matching END_GROUP number),
        // reject unterminated/mismatched ones. Past the depth cap the
        // datagram falls back to Python (deep flag) rather than being
        // rejected — the fallback decoder accepts deeper nesting.
        if (depth >= kPbSkipMaxDepth) {
          ok = false;
          deep = true;
          return;
        }
        uint32_t f2, w2;
        while (true) {
          if (!tag(&f2, &w2)) {
            ok = false;  // EOF inside a group
            return;
          }
          if (w2 == 4) {
            if (f2 != field) ok = false;
            return;
          }
          skip(f2, w2, depth + 1);
          if (!ok) return;
        }
      }
      case 5: p = (end - p >= 4) ? p + 4 : (ok = false, end); break;
      default: ok = false;  // bare END_GROUP (4) or invalid 6/7
    }
  }
};

// parse one map<string,string> entry {1: key, 2: value} into raw
// (key, value) — kept raw so map semantics (last entry wins per key)
// can be applied before formatting
bool parse_tag_entry(const uint8_t* s, size_t n,
                     std::pair<std::string, std::string>* out,
                     bool* deep = nullptr) {
  PbReader r{s, s + n};
  const uint8_t *k = nullptr, *v = nullptr;
  size_t kn = 0, vn = 0;
  uint32_t f, wt;
  while (r.tag(&f, &wt)) {
    if (f == 1 && wt == 2) {
      r.bytes(&k, &kn);
    } else if (f == 2 && wt == 2) {
      r.bytes(&v, &vn);
    } else {
      r.skip(f, wt);
    }
    if (!r.ok) break;
  }
  if (deep != nullptr) *deep = *deep || r.deep;
  if (!r.ok) return false;
  // proto3 `string` fields must be valid UTF-8 — the Python decoder
  // rejects the whole message otherwise, and the key records these
  // bytes land in are strict-decoded downstream
  if (!utf8_valid(k, kn) || !utf8_valid(v, vn)) return false;
  // a map entry may omit field 1 or 2 entirely, leaving k/v nullptr:
  // clear() the target instead of assign(nullptr, 0), which is UB
  // (round-5 advisory NA01)
  if (k) out->first.assign(reinterpret_cast<const char*>(k), kn);
  else out->first.clear();
  if (v) out->second.assign(reinterpret_cast<const char*>(v), vn);
  else out->second.clear();
  return true;
}

struct SsfSample {
  // proto3 enums are int32: varints truncate to the low 32 bits,
  // signed — matching the Python decoder (a 2^32+4 wire value IS
  // STATUS there, and must be here too)
  int32_t metric = 0;
  std::string name, message, unit;
  float value = 0.0f;
  float rate = 0.0f;
  int32_t scope = 0;
  std::vector<std::pair<std::string, std::string>> tags;  // raw k, v
};

// A known field whose wire type doesn't match its declaration is
// treated as an unknown field and skipped — proto3 parser semantics,
// which the Python decoder follows; diverging here would make the two
// paths accept different byte streams.
bool parse_ssf_sample(const uint8_t* s, size_t n, SsfSample* out,
                      bool* deep = nullptr) {
  PbReader r{s, s + n};
  uint32_t f, wt;
  while (r.tag(&f, &wt)) {
    const uint8_t* b;
    size_t bn;
    if (f == 1 && wt == 0) {                                  // Metric
      out->metric = static_cast<int32_t>(r.varint());
    } else if (f == 2 && wt == 2) {                           // name
      if (!r.bytes(&b, &bn) || !utf8_valid(b, bn)) return false;
      out->name.assign(reinterpret_cast<const char*>(b), bn);
    } else if (f == 3 && wt == 5) {                           // value
      out->value = r.f32();
    } else if (f == 5 && wt == 2) {                           // message
      if (!r.bytes(&b, &bn) || !utf8_valid(b, bn)) return false;
      out->message.assign(reinterpret_cast<const char*>(b), bn);
    } else if (f == 7 && wt == 5) {                           // rate
      out->rate = r.f32();
    } else if (f == 8 && wt == 2) {                           // tags
      if (!r.bytes(&b, &bn)) return false;
      out->tags.emplace_back();
      if (!parse_tag_entry(b, bn, &out->tags.back(), deep)) return false;
    } else if (f == 9 && wt == 2) {                           // unit
      if (!r.bytes(&b, &bn) || !utf8_valid(b, bn)) return false;
      out->unit.assign(reinterpret_cast<const char*>(b), bn);
    } else if (f == 10 && wt == 0) {                          // Scope
      out->scope = static_cast<int32_t>(r.varint());
    } else {
      r.skip(f, wt);
    }
    if (!r.ok) {
      if (deep != nullptr) *deep = *deep || r.deep;
      return false;
    }
  }
  if (deep != nullptr) *deep = *deep || r.deep;
  return r.ok;
}

// time-unit scale to milliseconds (ssf/__init__.py TIME_UNITS; "\xc2\xb5s"
// is UTF-8 "µs")
bool time_unit_ms(const std::string& u, double* scale_ms) {
  if (u == "ns") *scale_ms = 1e-6;
  else if (u == "\xc2\xb5s" || u == "us") *scale_ms = 1e-3;
  else if (u == "ms") *scale_ms = 1.0;
  else if (u == "s") *scale_ms = 1e3;
  else return false;
  return true;
}

// Fill a ParsedMetric from one decoded sample; mirrors
// sample_to_metric. Returns false when the sample is skipped (no name
// / unknown type) — the Python twin returns None for those.
bool sample_to_parsed(const SsfSample& s, ParsedMetric* m) {
  if (s.name.empty()) return false;
  switch (s.metric) {
    case 0: m->mtype = MT_COUNTER; break;
    case 1: m->mtype = MT_GAUGE; break;
    case 2: m->mtype = MT_HISTOGRAM; break;
    case 3: m->mtype = MT_SET; break;
    default: return false;  // STATUS is pre-filtered; unknown skipped
  }
  m->value = s.value;
  double scale_ms;
  if (m->mtype == MT_HISTOGRAM && time_unit_ms(s.unit, &scale_ms)) {
    m->mtype = MT_TIMER;
    m->value = static_cast<double>(s.value) * scale_ms;
  }
  m->rate = (s.rate != 0.0f) ? s.rate : 1.0;
  m->scope = (s.scope >= 0 && s.scope <= 2)
                 ? static_cast<uint8_t>(s.scope)
                 : static_cast<uint8_t>(SC_MIXED);
  m->name = s.name;
  if (m->mtype == MT_SET) m->member = s.message;
  // proto3 map semantics: for duplicate keys on the wire, the LAST
  // entry wins (what the Python decoder's dict does) — dedupe on the
  // raw key before formatting, or the native and fallback paths would
  // build different metric identities for the same datagram
  std::vector<std::string> formatted;
  formatted.reserve(s.tags.size());
  for (size_t i = 0; i < s.tags.size(); i++) {
    bool overwritten = false;
    for (size_t j = i + 1; j < s.tags.size(); j++)
      if (s.tags[j].first == s.tags[i].first) {
        overwritten = true;
        break;
      }
    if (overwritten) continue;
    std::string f = s.tags[i].first;
    if (!s.tags[i].second.empty()) {
      f.push_back(':');
      f.append(s.tags[i].second);
    }
    formatted.push_back(std::move(f));
  }
  // sorted, comma-joined — UTF-8 byte order equals code point order,
  // so std::sort matches Python's sorted()
  std::sort(formatted.begin(), formatted.end());
  m->joined_tags.clear();
  for (size_t i = 0; i < formatted.size(); i++) {
    if (i) m->joined_tags.push_back(',');
    m->joined_tags.append(formatted[i]);
  }
  m->digest = metric_digest32(
      reinterpret_cast<const uint8_t*>(m->name.data()), m->name.size(),
      m->mtype, m->joined_tags);
  return true;
}

// Decode + stage one SSF datagram. Returns 1 when handled natively,
// 0 when the caller must use the Python path (STATUS samples present,
// or unknown-field nesting past kPbSkipMaxDepth — the Python decoder
// accepts deeper well-formed groups, so erroring would diverge),
// -1 on malformed protobuf (counted; caller should count an ssf error).
int handle_ssf(Bridge* br, LocalStage* st, const uint8_t* data,
               size_t len) {
  PbReader r{data, data + len};
  std::vector<SsfSample> samples;
  bool indicator = false, error = false, deep = false;
  int64_t start_ts = 0, end_ts = 0;
  std::string service;
  uint32_t f, wt;
  std::pair<std::string, std::string> scratch_tag;
  auto fail = [&]() -> int {
    if (deep || r.deep) {
      br->ssf_fallbacks.fetch_add(1, std::memory_order_relaxed);
      return 0;
    }
    return -1;
  };
  while (r.tag(&f, &wt)) {
    const uint8_t* b;
    size_t bn;
    if (f == 5 && wt == 0) {
      start_ts = static_cast<int64_t>(r.varint());
    } else if (f == 6 && wt == 0) {
      end_ts = static_cast<int64_t>(r.varint());
    } else if (f == 7 && wt == 0) {
      error = r.varint() != 0;
    } else if (f == 8 && wt == 2) {                        // service
      if (!r.bytes(&b, &bn) || !utf8_valid(b, bn)) return -1;
      service.assign(reinterpret_cast<const char*>(b), bn);
    } else if (f == 9 && wt == 2) {
      // span-level tags: unused by the metric extraction, but KNOWN to
      // the schema — the Python decoder parses and validates every
      // known submessage/string field, so the native path must reject
      // what it would reject (a skipped-but-malformed entry was a
      // fuzz-found false accept)
      if (!r.bytes(&b, &bn)) return -1;
      if (!parse_tag_entry(b, bn, &scratch_tag, &deep)) return fail();
    } else if (f == 10 && wt == 0) {
      indicator = r.varint() != 0;
    } else if (f == 11 && wt == 2) {                       // span name
      if (!r.bytes(&b, &bn) || !utf8_valid(b, bn)) return -1;
    } else if (f == 12 && wt == 2) {                       // metrics
      if (!r.bytes(&b, &bn)) return -1;
      samples.emplace_back();
      if (!parse_ssf_sample(b, bn, &samples.back(), &deep))
        return fail();
    } else {
      r.skip(f, wt);
    }
    if (!r.ok) return fail();
  }
  if (!r.ok) return fail();
  // STATUS samples become service checks in Python — whole-datagram
  // fallback so one span never lands half-natively
  for (const SsfSample& s : samples)
    if (s.metric == 4) {
      br->ssf_fallbacks.fetch_add(1, std::memory_order_relaxed);
      return 0;
    }
  br->ssf_spans.fetch_add(1, std::memory_order_relaxed);
  ParsedMetric m;
  for (const SsfSample& s : samples)
    if (sample_to_parsed(s, &m)) stage_parsed(br, st, m);
  if (indicator && !br->indicator_timer.empty() && start_ts && end_ts) {
    // indicator_timer(): duration timer tagged service/error
    m.mtype = MT_TIMER;
    m.value = static_cast<double>(end_ts >= start_ts ? end_ts - start_ts
                                                     : 0) / 1e6;
    m.rate = 1.0;
    m.scope = SC_MIXED;
    m.name = br->indicator_timer;
    std::string etag = error ? "error:true" : "error:false";
    std::string stag = "service:" + service;
    m.joined_tags = etag < stag ? etag + "," + stag : stag + "," + etag;
    m.digest = metric_digest32(
        reinterpret_cast<const uint8_t*>(m.name.data()), m.name.size(),
        m.mtype, m.joined_tags);
    stage_parsed(br, st, m);
  }
  return 1;
}

// recvmmsg burst machinery shared by the statsd and SSF reader loops.
struct RecvBatch {
  static constexpr int VLEN = 64;
  std::vector<std::vector<uint8_t>> bufs;
  std::vector<mmsghdr> msgs;
  std::vector<iovec> iovs;

  explicit RecvBatch(size_t max_dgram)
      : bufs(VLEN), msgs(VLEN), iovs(VLEN) {
    for (int i = 0; i < VLEN; i++) {
      bufs[i].resize(max_dgram);
      iovs[i].iov_base = bufs[i].data();
      iovs[i].iov_len = bufs[i].size();
      memset(&msgs[i], 0, sizeof(mmsghdr));
      msgs[i].msg_hdr.msg_iov = &iovs[i];
      msgs[i].msg_hdr.msg_iovlen = 1;
    }
  }
};

// A burst's share of its reader's tallies, added once the burst is
// staged: `t0` is when the receive returned.
inline void tally_burst(ReaderStat* rs, int packets, uint64_t lines,
                        uint64_t t0) {
  rs->packets.fetch_add(static_cast<uint64_t>(packets),
                        std::memory_order_relaxed);
  rs->lines.fetch_add(lines, std::memory_order_relaxed);
  rs->busy_ns.fetch_add(mono_ns() - t0, std::memory_order_relaxed);
}

void reader_loop(Bridge* br, int sock, ReaderStat* rs) {
  LocalStage st;
  RecvBatch rb(br->max_packet);
  pollfd pfd{sock, POLLIN, 0};
  while (!br->stop.load(std::memory_order_relaxed)) {
    int pr = poll(&pfd, 1, 100);
    if (pr <= 0) continue;
    int n = recvmmsg(sock, rb.msgs.data(), RecvBatch::VLEN, MSG_DONTWAIT,
                     nullptr);
    if (n <= 0) continue;
    uint64_t t0 = mono_ns(), lines0 = st.lines;
    // stamped before counted: a sender that has seen `packets` reach
    // its datagram knows every later datagram takes a later number
    uint32_t first = arrive(br, n);
    br->packets.fetch_add(n, std::memory_order_relaxed);
    for (int i = 0; i < n; i++) {
      st.order = first + static_cast<uint32_t>(i);
      handle_buffer(br, &st, rb.bufs[i].data(), rb.msgs[i].msg_len);
    }
    st.flush(br);
    tally_burst(rs, n, st.lines - lines0, t0);
  }
}

void route_ssf_other(Bridge* br, const uint8_t* data, size_t len) {
  std::lock_guard<std::mutex> g(br->ssf_other_mu);
  if (br->ssf_other.size() >= br->ssf_other_cap) {
    br->ssf_other_drops++;
    return;
  }
  br->ssf_other.emplace_back(reinterpret_cast<const char*>(data), len);
}

// The SSF span listener: one datagram = one SSFSpan protobuf, decoded
// and staged natively; fallback datagrams queue for the Python span
// pipeline (Server.ReadSSFPacketSocket's C++ twin).
void ssf_reader_loop(Bridge* br, int sock, ReaderStat* rs) {
  LocalStage st;
  RecvBatch rb(br->ssf_max_dgram);
  pollfd pfd{sock, POLLIN, 0};
  while (!br->stop.load(std::memory_order_relaxed)) {
    int pr = poll(&pfd, 1, 100);
    if (pr <= 0) continue;
    int n = recvmmsg(sock, rb.msgs.data(), RecvBatch::VLEN, MSG_DONTWAIT,
                     nullptr);
    if (n <= 0) continue;
    uint64_t t0 = mono_ns();
    uint32_t first = arrive(br, n);
    br->packets.fetch_add(n, std::memory_order_relaxed);
    for (int i = 0; i < n; i++) {
      st.order = first + static_cast<uint32_t>(i);
      int rc = handle_ssf(br, &st, rb.bufs[i].data(), rb.msgs[i].msg_len);
      if (rc == 0)
        route_ssf_other(br, rb.bufs[i].data(), rb.msgs[i].msg_len);
      else if (rc < 0)
        br->ssf_errors.fetch_add(1, std::memory_order_relaxed);
    }
    st.flush(br);
    tally_burst(rs, n, 0, t0);  // spans carry no lines
  }
}

// The frame layout of an SSF stream as ssf/framing.py defines it: one
// version byte, the payload's length, the SSFSpan protobuf. Each
// constant MUST stay equal to its Python twin (VERSION_BYTE,
// LENGTH_BYTES, LENGTH_LITTLE_ENDIAN, MAX_FRAME_LENGTH; vlint NA03).
constexpr int kSsfFrameVersion = 0;
constexpr int kSsfFrameLengthBytes = 4;
constexpr int kSsfFrameLengthLittleEndian = 1;
constexpr int kSsfMaxFrameLength = 16 * 1024 * 1024;
constexpr size_t kSsfFrameHeader = 1 + kSsfFrameLengthBytes;

inline size_t ssf_frame_length(const uint8_t* p) {
  size_t v = 0;
  for (int i = 0; i < kSsfFrameLengthBytes; i++) {
    int at = kSsfFrameLengthLittleEndian ? kSsfFrameLengthBytes - 1 - i : i;
    v = (v << 8) | p[at];
  }
  return v;
}

// One accepted connection of a framed SSF stream (Server.ReadSSFStream
// Socket's C++ twin, server.py:_read_ssf_stream's): whatever the socket
// has is read into one buffer and cut into frames, every whole frame
// goes through handle_ssf with ONE LocalStage, flushed into the rings
// once a read. A bad version byte, an oversize length, a malformed
// protobuf or a close inside a frame counts one ssf error and closes
// this connection only.
void ssf_stream_conn_loop(Bridge* br, int fd) {
  LocalStage st;
  std::vector<uint8_t> buf(1 << 16);
  size_t have = 0;
  bool bad = false;
  pollfd pfd{fd, POLLIN, 0};
  while (!bad && !br->stop.load(std::memory_order_relaxed)) {
    int pr = poll(&pfd, 1, 100);
    if (pr <= 0) continue;
    ssize_t n = recv(fd, buf.data() + have, buf.size() - have, 0);
    if (n < 0) {
      if (errno == EINTR || errno == EAGAIN) continue;
      bad = true;
      break;
    }
    if (n == 0) {
      bad = have > 0;  // closed inside a frame
      break;
    }
    have += static_cast<size_t>(n);
    uint64_t t0 = mono_ns();
    // one arrival number a read: a connection's frames keep their
    // order on its one stage
    st.order = arrive(br, 1);
    size_t off = 0;
    uint64_t frames = 0;
    while (off < have) {
      if (buf[off] != kSsfFrameVersion) {
        bad = true;
        break;
      }
      if (have - off < kSsfFrameHeader) break;
      size_t len = ssf_frame_length(&buf[off + 1]);
      if (len > static_cast<size_t>(kSsfMaxFrameLength)) {
        bad = true;
        break;
      }
      size_t whole = kSsfFrameHeader + len;
      if (have - off < whole) {
        // the frame's tail is still on its way: room for all of it
        if (buf.size() < whole) buf.resize(whole);
        break;
      }
      const uint8_t* body = &buf[off + kSsfFrameHeader];
      int rc = handle_ssf(br, &st, body, len);
      frames++;
      off += whole;
      if (rc == 0) {
        route_ssf_other(br, body, len);
      } else if (rc < 0) {
        bad = true;
        break;
      }
    }
    st.flush(br);
    if (off > 0) {
      memmove(buf.data(), buf.data() + off, have - off);
      have -= off;
    }
    br->ssf_stream_frames.fetch_add(frames, std::memory_order_relaxed);
    br->ssf_stream_read_ns.fetch_add(mono_ns() - t0,
                                     std::memory_order_relaxed);
  }
  if (bad) {
    br->ssf_errors.fetch_add(1, std::memory_order_relaxed);
    br->ssf_stream_conn_errors.fetch_add(1, std::memory_order_relaxed);
  }
  close(fd);
  std::lock_guard<std::mutex> g(br->stream_mu);
  br->stream_active--;
  br->stream_cv.notify_all();
}

// Accepts on a listening stream socket the server bound (unix:// or
// tcp://; `lsock` is the bridge's own dup of it) and gives every
// connection a reader thread of its own.
void ssf_stream_accept_loop(Bridge* br, int lsock) {
  pollfd pfd{lsock, POLLIN, 0};
  while (!br->stop.load(std::memory_order_relaxed)) {
    int pr = poll(&pfd, 1, 100);
    if (pr <= 0) continue;
    int fd = accept(lsock, nullptr, nullptr);
    if (fd < 0) continue;
    br->ssf_stream_conns.fetch_add(1, std::memory_order_relaxed);
    {
      std::lock_guard<std::mutex> g(br->stream_mu);
      br->stream_active++;
    }
    std::thread(ssf_stream_conn_loop, br, fd).detach();
  }
}

// Readers and acceptors joined, every accepted connection closed.
void stop_threads(Bridge* br) {
  br->stop.store(true);
  for (auto& t : br->readers)
    if (t.joinable()) t.join();
  br->readers.clear();
  std::unique_lock<std::mutex> g(br->stream_mu);
  br->stream_cv.wait(g, [br] { return br->stream_active == 0; });
}

}  // namespace

// ================================================================ C ABI

extern "C" {

void* vtpu_create(int32_t histo_slots, int32_t counter_slots,
                  int32_t gauge_slots, int32_t set_slots,
                  int32_t hll_precision, int32_t idle_ttl,
                  int32_t ring_capacity, int32_t max_packet) {
  Bridge* br = new Bridge();
  static std::atomic<uint64_t> next_instance{1};
  br->instance_id = next_instance.fetch_add(1, std::memory_order_relaxed);
  int32_t caps[NUM_BANKS] = {histo_slots, counter_slots, gauge_slots,
                             set_slots};
  for (int i = 0; i < NUM_BANKS; i++) {
    br->banks[i].init(caps[i]);
    for (int w = 0; w < RING_WAYS; w++)
      br->rings[i][w].init(
          static_cast<size_t>(ring_capacity) / RING_WAYS + 1);
  }
  br->hll_precision = hll_precision;
  br->idle_ttl = idle_ttl;
  br->max_packet = max_packet;
  return br;
}

void vtpu_destroy(void* h) {
  Bridge* br = static_cast<Bridge*>(h);
  stop_threads(br);
  for (int s : br->socks) close(s);
  delete br;
}

// Feed one raw packet (possibly multiple '\n'-separated lines) from the
// calling thread — the test/slow-path entry, same code as the readers.
void vtpu_handle_packet(void* h, const uint8_t* data, int32_t len) {
  Bridge* br = static_cast<Bridge*>(h);
  thread_local LocalStage st;
  st.order = arrive(br, 1);
  br->packets.fetch_add(1, std::memory_order_relaxed);
  handle_buffer(br, &st, data, static_cast<size_t>(len));
  st.flush(br);
}

// Decode one SSF span datagram and stage its embedded samples natively.
// Returns 1 = handled, 0 = caller must use the Python span path for
// this datagram, -1 = malformed protobuf.
int32_t vtpu_handle_ssf(void* h, const uint8_t* data, int32_t len) {
  Bridge* br = static_cast<Bridge*>(h);
  thread_local LocalStage st;
  st.order = arrive(br, 1);
  int rc = handle_ssf(br, &st, data, static_cast<size_t>(len));
  if (rc == 1) st.flush(br);
  return rc;
}

// Configure the indicator-span duration timer (config key
// indicator_span_timer_name). Must be called before readers start.
void vtpu_set_indicator_timer(void* h, const char* name) {
  Bridge* br = static_cast<Bridge*>(h);
  br->indicator_timer = name ? name : "";
}

// Start n SO_REUSEPORT UDP reader threads on host:port. Returns bound
// port (useful with port 0) or -errno.
static int32_t open_udp_readers(Bridge* br, const char* host,
                                int32_t port, int32_t n_readers,
                                int32_t rcvbuf,
                                void (*loop)(Bridge*, int, ReaderStat*)) {
  bool v6 = strchr(host, ':') != nullptr;
  int bound = -1;
  for (int r = 0; r < n_readers; r++) {
    int fd = socket(v6 ? AF_INET6 : AF_INET, SOCK_DGRAM, 0);
    if (fd < 0) return -errno;
    int one = 1;
    setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
#ifdef SO_REUSEPORT
    setsockopt(fd, SOL_SOCKET, SO_REUSEPORT, &one, sizeof(one));
#endif
    if (rcvbuf > 0)
      setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &rcvbuf, sizeof(rcvbuf));
    int rc;
    if (v6) {
      sockaddr_in6 sa{};
      sa.sin6_family = AF_INET6;
      sa.sin6_port = htons(static_cast<uint16_t>(bound > 0 ? bound : port));
      if (inet_pton(AF_INET6, host, &sa.sin6_addr) != 1) {
        close(fd);
        return -EINVAL;  // hostnames must be resolved by the caller
      }
      rc = bind(fd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa));
      if (rc == 0 && bound < 0) {
        socklen_t sl = sizeof(sa);
        getsockname(fd, reinterpret_cast<sockaddr*>(&sa), &sl);
        bound = ntohs(sa.sin6_port);
      }
    } else {
      sockaddr_in sa{};
      sa.sin_family = AF_INET;
      sa.sin_port = htons(static_cast<uint16_t>(bound > 0 ? bound : port));
      if (inet_pton(AF_INET, host, &sa.sin_addr) != 1) {
        close(fd);
        return -EINVAL;  // hostnames must be resolved by the caller
      }
      rc = bind(fd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa));
      if (rc == 0 && bound < 0) {
        socklen_t sl = sizeof(sa);
        getsockname(fd, reinterpret_cast<sockaddr*>(&sa), &sl);
        bound = ntohs(sa.sin_port);
      }
    }
    if (rc != 0) {
      int e = errno;
      close(fd);
      return -e;
    }
    br->socks.push_back(fd);
    int ri = br->n_readers.fetch_add(1, std::memory_order_relaxed);
    br->readers.emplace_back(
        loop, br, fd, &br->reader_stats[std::min(ri, MAX_READERS - 1)]);
  }
  return bound;
}

int32_t vtpu_start_udp(void* h, const char* host, int32_t port,
                       int32_t n_readers, int32_t rcvbuf) {
  Bridge* br = static_cast<Bridge*>(h);
  int32_t bound = open_udp_readers(br, host, port, n_readers, rcvbuf,
                                   reader_loop);
  if (bound >= 0) br->bound_port = bound;
  return bound;
}

// Start the native SSF span listener (one datagram = one SSFSpan).
// max_dgram sizes the receive buffers (trace_max_length_bytes).
int32_t vtpu_start_ssf_udp(void* h, const char* host, int32_t port,
                           int32_t n_readers, int32_t rcvbuf,
                           int32_t max_dgram) {
  Bridge* br = static_cast<Bridge*>(h);
  if (max_dgram > 0) br->ssf_max_dgram = max_dgram;
  int32_t bound = open_udp_readers(br, host, port, n_readers, rcvbuf,
                                   ssf_reader_loop);
  if (bound >= 0) br->ssf_bound_port = bound;
  return bound;
}

// Start the native reader of framed SSF streams on a listening stream
// socket the caller bound and listens on (unix:// or tcp://). The bridge
// accepts on a dup of `listen_fd`, so the caller may close its own; every
// accepted connection is the bridge's, closed by vtpu_stop. Returns 0 or
// -errno.
int32_t vtpu_start_ssf_stream(void* h, int32_t listen_fd) {
  Bridge* br = static_cast<Bridge*>(h);
  int fd = dup(listen_fd);
  if (fd < 0) return -errno;
  br->socks.push_back(fd);
  br->readers.emplace_back(ssf_stream_accept_loop, br, fd);
  return 0;
}

// Drain fallback SSF datagrams (STATUS-carrying spans) as u32le
// length-prefixed records for the Python span pipeline.
int32_t vtpu_drain_ssf_other(void* h, uint8_t* buf, int32_t buf_len) {
  Bridge* br = static_cast<Bridge*>(h);
  std::lock_guard<std::mutex> g(br->ssf_other_mu);
  int32_t off = 0;
  while (!br->ssf_other.empty()) {
    const std::string& s = br->ssf_other.front();
    int32_t need = 4 + static_cast<int32_t>(s.size());
    if (off + need > buf_len) break;
    uint32_t sl = static_cast<uint32_t>(s.size());
    memcpy(buf + off, &sl, 4);
    off += 4;
    memcpy(buf + off, s.data(), sl);
    off += sl;
    br->ssf_other.pop_front();
  }
  return off;
}

void vtpu_stop(void* h) {
  Bridge* br = static_cast<Bridge*>(h);
  stop_threads(br);
  for (int s : br->socks) close(s);
  br->socks.clear();
}

// Drain up to max_n staged samples for `bank` into caller arrays.
// histo/counter: a=values  b=weights;  gauge: a=values  c=arrival number;
// set: a=rho  c=register index.
int32_t vtpu_poll(void* h, int32_t bank, int32_t max_n, int32_t* slots,
                  float* a, float* b, int32_t* c) {
  Bridge* br = static_cast<Bridge*>(h);
  size_t got = 0;
  for (int w = 0; w < RING_WAYS && got < static_cast<size_t>(max_n); w++)
    got += br->rings[bank][w].pop(slots + got, a + got, b + got, c + got,
                                  static_cast<size_t>(max_n) - got);
  return static_cast<int32_t>(got);
}

// Drain newly-interned keys as packed records:
//   bank u8 | mtype u8 | scope u8 | slot i32le | name_len u16le | name |
//   tags_len u16le | tags
// Returns bytes written; 0 when empty. Records are never split.
int32_t vtpu_drain_new_keys(void* h, uint8_t* buf, int32_t buf_len) {
  Bridge* br = static_cast<Bridge*>(h);
  std::lock_guard<std::mutex> g(br->newkeys_mu);
  int32_t off = 0;
  while (!br->newkeys.empty()) {
    const NewKey& nk = br->newkeys.front();
    int32_t need = 3 + 4 + 2 + static_cast<int32_t>(nk.name.size()) + 2 +
                   static_cast<int32_t>(nk.tags.size());
    if (off + need > buf_len) break;
    buf[off++] = nk.bank;
    buf[off++] = nk.mtype;
    buf[off++] = nk.scope;
    memcpy(buf + off, &nk.slot, 4);
    off += 4;
    uint16_t nl = static_cast<uint16_t>(nk.name.size());
    memcpy(buf + off, &nl, 2);
    off += 2;
    memcpy(buf + off, nk.name.data(), nl);
    off += nl;
    uint16_t tl = static_cast<uint16_t>(nk.tags.size());
    memcpy(buf + off, &tl, 2);
    off += 2;
    memcpy(buf + off, nk.tags.data(), tl);
    off += tl;
    br->newkeys.pop_front();
  }
  return off;
}

// Drain slow-path lines (events, service checks, py-float oddities) as
// u16le length-prefixed raw byte strings. Returns bytes written.
int32_t vtpu_drain_other(void* h, uint8_t* buf, int32_t buf_len) {
  Bridge* br = static_cast<Bridge*>(h);
  std::lock_guard<std::mutex> g(br->other_mu);
  int32_t off = 0;
  while (!br->other.empty()) {
    const std::string& s = br->other.front();
    int32_t need = 2 + static_cast<int32_t>(s.size());
    if (off + need > buf_len) break;
    uint16_t sl = static_cast<uint16_t>(s.size());
    memcpy(buf + off, &sl, 2);
    off += 2;
    memcpy(buf + off, s.data(), sl);
    off += sl;
    br->other.pop_front();
  }
  return off;
}

// Bulk-read per-slot scopes for `bank` (flush-time snapshot).
void vtpu_slot_scopes(void* h, int32_t bank, uint8_t* out, int32_t n) {
  Bridge* br = static_cast<Bridge*>(h);
  BankMeta& bm = br->banks[bank];
  int32_t lim = std::min(n, bm.capacity);
  for (int32_t i = 0; i < lim; i++)
    out[i] = bm.scope[i].load(std::memory_order_relaxed);
}

// Advance `bank`'s interval counter and evict keys idle > idle_ttl
// intervals (KeyInterner.advance_interval's eviction). Returns number
// evicted. Gauge advance also resets the per-interval gauge sequence.
int32_t vtpu_advance_interval(void* h, int32_t bank) {
  Bridge* br = static_cast<Bridge*>(h);
  BankMeta& bm = br->banks[bank];
  uint32_t now = bm.interval.fetch_add(1, std::memory_order_relaxed) + 1;
  // any eviction below may reassign slots: invalidate thread-local key
  // caches up front (publishes before the frees become visible)
  br->intern_epoch.fetch_add(1, std::memory_order_acq_rel);
  if (br->idle_ttl <= 0 || now < static_cast<uint32_t>(br->idle_ttl))
    return 0;
  uint32_t horizon = now - static_cast<uint32_t>(br->idle_ttl);
  int32_t evicted = 0;
  for (int s = 0; s < NUM_SHARDS; s++) {
    Shard& sh = br->shards[s];
    std::lock_guard<std::mutex> g(sh.mu);
    for (auto it = sh.map[bank].begin(); it != sh.map[bank].end();) {
      int32_t slot = it->second;
      if (bm.last_interval[slot].load(std::memory_order_relaxed) < horizon) {
        {
          std::lock_guard<std::mutex> fg(bm.free_mu);
          bm.free_slots.push_back(slot);
        }
        bm.key_count.fetch_add(-1, std::memory_order_relaxed);
        it = sh.map[bank].erase(it);
        evicted++;
      } else {
        ++it;
      }
    }
  }
  bm.keys_evicted.fetch_add(static_cast<uint64_t>(evicted),
                            std::memory_order_relaxed);
  return evicted;
}

// Intern one key from the Python side (the slow path / ssfmetrics bridge /
// global-tier Combine all reach interning through here in native mode).
// Returns the slot, or -1 when the bank is full.
int32_t vtpu_intern(void* h, int32_t mtype, int32_t scope,
                    const uint8_t* name, int32_t name_len,
                    const uint8_t* tags, int32_t tags_len) {
  Bridge* br = static_cast<Bridge*>(h);
  thread_local ParsedMetric m;
  thread_local std::string keybuf;
  m.mtype = static_cast<MType>(mtype);
  m.scope = static_cast<uint8_t>(scope);
  m.name.assign(reinterpret_cast<const char*>(name),
                static_cast<size_t>(name_len));
  m.joined_tags.assign(reinterpret_cast<const char*>(tags),
                       static_cast<size_t>(tags_len));
  uint32_t hh = fnv1a_32(name, static_cast<size_t>(name_len), FNV32_OFFSET);
  const char* tn = MTYPE_NAMES[mtype];
  hh = fnv1a_32(reinterpret_cast<const uint8_t*>(tn), strlen(tn), hh);
  hh = fnv1a_32(tags, static_cast<size_t>(tags_len), hh);
  m.digest = hh;
  build_key(m, &keybuf);
  return intern_key(br, m, keybuf);
}

// Install the tags_exclude list: '\n'-joined tag names. MUST be called
// before vtpu_start_udp (readers snapshot nothing; the list is read
// lock-free on the hot path).
void vtpu_set_tags_exclude(void* h, const uint8_t* packed, int32_t len) {
  Bridge* br = static_cast<Bridge*>(h);
  br->tags_exclude.clear();
  size_t start = 0;
  std::string all(reinterpret_cast<const char*>(packed),
                  static_cast<size_t>(len));
  while (start <= all.size() && len > 0) {
    size_t nl = all.find('\n', start);
    size_t end = (nl == std::string::npos) ? all.size() : nl;
    if (end > start) br->tags_exclude.emplace_back(all, start, end - start);
    if (nl == std::string::npos) break;
    start = nl + 1;
  }
}

int64_t vtpu_key_count(void* h, int32_t bank) {
  return static_cast<Bridge*>(h)->banks[bank].key_count.load();
}

// stats[0..8] = packets, lines, samples, parse_errors, slow_routed,
//               drops_no_slot(sum), ring_drops(sum), other_drops,
//               pending_other;  [9..13] = ssf_spans, ssf_fallbacks,
//               ssf_errors, ssf_other_drops, pending_ssf_other;
//               [14..18] = the framed-stream readers' frames, connections
//               accepted, connections closed on error, ns inside
//               handle_ssf + staging, ns waited for ring room (always 0);
//               [19] = ns inside intern_key's slow path; then per bank
//               (histo, counter, gauge, set) [20..23] = keys holding a
//               slot, [24..27] = keys minted, [28..31] = keys the idle
//               TTL evicted (running totals), [32..35] = the high water
//               of the bank's fullest sub-ring since vtpu_take_ring_high
//               last reset it (samples; a sub-ring holds
//               ring_capacity / RING_WAYS + 1)
constexpr int kStatsFields = 36;  // ingest/native.py:STATS_FIELDS (NA04)


void vtpu_stats(void* h, uint64_t* out) {
  Bridge* br = static_cast<Bridge*>(h);
  out[0] = br->packets.load();
  out[1] = br->lines.load();
  out[2] = br->samples.load();
  out[3] = br->parse_errors.load();
  out[4] = br->slow_routed.load();
  uint64_t no_slot = 0, ring_drops = 0;
  for (int i = 0; i < NUM_BANKS; i++) {
    no_slot += br->banks[i].drops_no_slot.load();
    size_t high = 0;
    for (int w = 0; w < RING_WAYS; w++) {
      std::lock_guard<std::mutex> g(br->rings[i][w].mu);
      ring_drops += br->rings[i][w].drops;
      high = std::max(high, br->rings[i][w].high);
    }
    out[32 + i] = high;
  }
  out[5] = no_slot;
  out[6] = ring_drops;
  out[9] = br->ssf_spans.load();
  out[10] = br->ssf_fallbacks.load();
  out[11] = br->ssf_errors.load();
  {
    std::lock_guard<std::mutex> sg(br->ssf_other_mu);
    out[12] = br->ssf_other_drops;
    out[13] = br->ssf_other.size();
  }
  out[14] = br->ssf_stream_frames.load();
  out[15] = br->ssf_stream_conns.load();
  out[16] = br->ssf_stream_conn_errors.load();
  out[17] = br->ssf_stream_read_ns.load();
  out[18] = br->ssf_stream_wait_ns.load();
  out[19] = br->intern_ns.load();
  for (int i = 0; i < NUM_BANKS; i++) {
    out[20 + i] = static_cast<uint64_t>(br->banks[i].key_count.load());
    out[24 + i] = br->banks[i].keys_interned.load();
    out[28 + i] = br->banks[i].keys_evicted.load();
  }
  std::lock_guard<std::mutex> g(br->other_mu);
  out[7] = br->other_drops;
  out[8] = br->other.size();
}

// out[bank] = the high water of the bank's fullest sub-ring since the
// last call, which this call resets (the flush's read, once a tick).
void vtpu_take_ring_high(void* h, uint64_t* out) {
  Bridge* br = static_cast<Bridge*>(h);
  for (int i = 0; i < NUM_BANKS; i++) {
    size_t most = 0;
    for (int w = 0; w < RING_WAYS; w++)
      most = std::max(most, br->rings[i][w].take_high());
    out[i] = most;
  }
}

// Samples one sub-ring holds (every ring of the bridge is as long).
int32_t vtpu_ring_way_capacity(void* h) {
  return static_cast<int32_t>(static_cast<Bridge*>(h)->rings[0][0].cap);
}

// Each UDP reader's running totals, in the order the readers were
// started (statsd listeners first): out[3 * r + 0..2] = datagrams,
// lines, busy ns (receive returned -> burst staged). Returns how many
// readers were written (at most max_readers).
int32_t vtpu_reader_stats(void* h, uint64_t* out, int32_t max_readers) {
  Bridge* br = static_cast<Bridge*>(h);
  int n = std::min({br->n_readers.load(std::memory_order_relaxed),
                    MAX_READERS, static_cast<int>(max_readers)});
  for (int r = 0; r < n; r++) {
    const ReaderStat& rs = br->reader_stats[r];
    out[3 * r] = rs.packets.load(std::memory_order_relaxed);
    out[3 * r + 1] = rs.lines.load(std::memory_order_relaxed);
    out[3 * r + 2] = rs.busy_ns.load(std::memory_order_relaxed);
  }
  return n;
}

// The next arrival number, for a gauge that reaches the engine by the
// Python path (a slow-path line, a fallback span): it is ordered among
// the datagrams by when it is processed.
int32_t vtpu_next_arrival(void* h) {
  return static_cast<int32_t>(arrive(static_cast<Bridge*>(h), 1));
}

// -------- conformance/testing helpers (stateless parse of one line) -----
// Returns the ParseVerdict. On P_METRIC fills the packed record:
//   mtype u8 | scope u8 | rate f64le | value f64le | digest u32le |
//   name_len u16le | name | tags_len u16le | tags |
//   member_len u16le | member
int32_t vtpu_parse_one(const uint8_t* data, int32_t len, uint8_t* buf,
                       int32_t buf_len, int32_t* out_len) {
  std::vector<std::pair<const uint8_t*, size_t>> secs, tags;
  ParsedMetric m;
  ParseVerdict v = parse_line(data, static_cast<size_t>(len), &m, &secs,
                              &tags);
  *out_len = 0;
  if (v != P_METRIC) return v;
  int32_t need = 1 + 1 + 8 + 8 + 4 + 2 +
                 static_cast<int32_t>(m.name.size()) + 2 +
                 static_cast<int32_t>(m.joined_tags.size()) + 2 +
                 static_cast<int32_t>(m.member.size());
  if (need > buf_len) return P_ERROR;
  int32_t off = 0;
  buf[off++] = m.mtype;
  buf[off++] = m.scope;
  memcpy(buf + off, &m.rate, 8);
  off += 8;
  memcpy(buf + off, &m.value, 8);
  off += 8;
  memcpy(buf + off, &m.digest, 4);
  off += 4;
  uint16_t nl = static_cast<uint16_t>(m.name.size());
  memcpy(buf + off, &nl, 2);
  off += 2;
  memcpy(buf + off, m.name.data(), nl);
  off += nl;
  uint16_t tl = static_cast<uint16_t>(m.joined_tags.size());
  memcpy(buf + off, &tl, 2);
  off += 2;
  memcpy(buf + off, m.joined_tags.data(), tl);
  off += tl;
  uint16_t ml = static_cast<uint16_t>(m.member.size());
  memcpy(buf + off, &ml, 2);
  off += 2;
  memcpy(buf + off, m.member.data(), ml);
  off += ml;
  *out_len = off;
  return P_METRIC;
}

// Parse-only throughput probe: parse the given newline-separated buffer
// `iters` times with no interning/staging; returns seconds elapsed.
double vtpu_bench_parse(const uint8_t* data, int32_t len, int32_t iters) {
  std::vector<std::pair<const uint8_t*, size_t>> secs, tags;
  ParsedMetric m;
  timespec t0, t1;
  clock_gettime(CLOCK_MONOTONIC, &t0);
  for (int32_t it = 0; it < iters; it++) {
    size_t i = 0;
    size_t n = static_cast<size_t>(len);
    while (i < n) {
      const uint8_t* nl =
          static_cast<const uint8_t*>(memchr(data + i, '\n', n - i));
      size_t ll = nl ? static_cast<size_t>(nl - (data + i)) : n - i;
      if (ll > 0) parse_line(data + i, ll, &m, &secs, &tags);
      i += ll + 1;
    }
  }
  clock_gettime(CLOCK_MONOTONIC, &t1);
  return (t1.tv_sec - t0.tv_sec) + (t1.tv_nsec - t0.tv_nsec) * 1e-9;
}

int32_t vtpu_bound_port(void* h) {
  return static_cast<Bridge*>(h)->bound_port;
}

int32_t vtpu_ssf_bound_port(void* h) {
  return static_cast<Bridge*>(h)->ssf_bound_port;
}

}  // extern "C"
