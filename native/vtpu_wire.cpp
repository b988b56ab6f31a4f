// vtpu_wire — the two native passes of the forward's wire, one a side
// (veneur_tpu/cluster/wire.py): the receiver's, over the bytes of a
// forwardrpc.MetricList for the global tier's import worker
// (BatchDecoder), and the sender's, from an export's columns to the
// bytes of a MetricList's `metrics` for a local's gRPC forwarder
// (encode_export).
//
// A translation unit of its own: it shares nothing with vtpu_ingest.cpp,
// starts no thread and keeps no state. The decoder's entry point reads a
// request's sketches out of its serialized bytes into columns the caller
// allocated; Python then builds decode_metric_batch's records from the
// columns, a dictionary lookup a sketch instead of a protobuf attribute a
// field. The encoder's writes every sketch of a flush's export into one
// buffer the caller allocated, from flat arrays, so that the sender holds
// no protobuf object a sketch or a centroid.
//
// What the decoder is sure of is the plain shape export_to_metrics writes
// (veneur_tpu/cluster/protos/metric.proto, fields in number order, each
// at most once, `tags` alone repeated, one member of the `value` oneof,
// centroids as repeated Centroid messages). A metric of any other shape
// (fields out of order or twice, an unknown field or wire type,
// `packed_centroids`, a `status_check`, a length that overruns its
// parent, more centroids than the caller's columns hold) is marked
// K_FALLBACK and left to the Python decoder, which reads it from the
// parsed message: the two decoders then agree by construction. Nothing
// outside [buf, buf + len) is ever read.
//
// The encoder writes that same plain shape, byte for byte what protobuf's
// serializer gives for export_to_metrics' objects (proto3: a scalar or an
// enum whose bits are zero is left out, a member of the oneof is written
// even when empty). An export with a metric it will not write (a counter
// that is no int64, bytes that do not fit the buffer) it refuses whole,
// and export_to_metrics writes it or raises, as it did before this pass.

#include <cstdint>
#include <cstring>

#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ != __ORDER_LITTLE_ENDIAN__
#error "vtpu_wire reads protobuf's little-endian fixed64 by memcpy"
#endif

namespace {

// kinds of a row; the first four are wire.IMPORT_* (a record's kind)
enum : int64_t {
  K_HISTOGRAM = 0, K_SET = 1, K_COUNTER = 2, K_GAUGE = 3,
  K_NONE = 4,       // no member of the oneof set: no record
  K_FALLBACK = 5,   // not the plain shape: Python decodes it
};

// what a row holds: five ints and five floats, each a column of its own
// (ints[col][row], floats[col][row]), so that Python takes a column as
// one flat list and builds no list a row
enum { I_KIND, I_KEY_OFF, I_KEY_LEN, I_A, I_B, N_INTS };
enum { F_MIN, F_MAX, F_SUM, F_COUNT, F_RECIP, N_FLOATS };

struct Row {
  int64_t* ints;     // &ints[0][row]
  double* floats;    // &floats[0][row]
  int64_t stride;    // rows a column
  int64_t& I(int col) const { return ints[col * stride]; }
  double& F(int col) const { return floats[col * stride]; }
};

enum : uint64_t { WT_VARINT = 0, WT_FIXED64 = 1, WT_BYTES = 2, WT_FIXED32 = 5 };

struct Span {
  const uint8_t* p;
  const uint8_t* end;
  bool done() const { return p >= end; }
};

inline bool read_varint(Span& s, uint64_t& v) {
  v = 0;
  for (int shift = 0; shift < 70; shift += 7) {
    if (s.p == s.end) return false;
    const uint8_t b = *s.p++;
    if (shift < 64) v |= static_cast<uint64_t>(b & 0x7f) << shift;
    if (!(b & 0x80)) return true;
  }
  return false;
}

inline bool read_tag(Span& s, uint64_t& field, uint64_t& wt) {
  uint64_t tag;
  if (!read_varint(s, tag)) return false;
  field = tag >> 3;
  wt = tag & 7;
  return true;
}

inline bool read_double(Span& s, double& v) {
  if (s.end - s.p < 8) return false;
  std::memcpy(&v, s.p, 8);
  s.p += 8;
  return true;
}

// the payload of a length-delimited field, inside its parent
inline bool read_bytes(Span& s, Span& sub) {
  uint64_t n;
  if (!read_varint(s, n)) return false;
  if (n > static_cast<uint64_t>(s.end - s.p)) return false;
  sub.p = s.p;
  sub.end = s.p + n;
  s.p = sub.end;
  return true;
}

inline bool skip_field(Span& s, uint64_t wt) {
  uint64_t v;
  Span sub;
  switch (wt) {
    case WT_VARINT: return read_varint(s, v);
    case WT_FIXED64: if (s.end - s.p < 8) return false; s.p += 8; return true;
    case WT_BYTES: return read_bytes(s, sub);
    case WT_FIXED32: if (s.end - s.p < 4) return false; s.p += 4; return true;
    default: return false;   // groups: not ours to walk
  }
}

struct Columns {
  float* means;
  float* weights;
  int64_t n;     // centroids written
  int64_t cap;
};

// metricpb.Centroid {1: mean, 2: weight}
bool centroid(Span s, Columns& c) {
  double mean = 0.0, weight = 0.0;
  uint64_t last = 0, field, wt;
  while (!s.done()) {
    if (!read_tag(s, field, wt) || field <= last || field > 2 ||
        wt != WT_FIXED64 || !read_double(s, field == 1 ? mean : weight))
      return false;
    last = field;
  }
  if (c.n >= c.cap) return false;
  // a double rounds to f32 as numpy's cast rounds it: the C conversion
  c.means[c.n] = static_cast<float>(mean);
  c.weights[c.n] = static_cast<float>(weight);
  c.n++;
  return true;
}

// metricpb.TDigest {1: repeated Centroid, 2-6: min max sum count recip}
bool tdigest(Span s, Columns& c, const Row& r) {
  uint64_t last = 0, field, wt;
  Span sub;
  while (!s.done()) {
    if (!read_tag(s, field, wt) || field == 0 || field < last)
      return false;
    if (field == 1) {
      if (wt != WT_BYTES || !read_bytes(s, sub) || !centroid(sub, c))
        return false;
    } else if (field <= 6) {
      if (field == last || wt != WT_FIXED64 ||
          !read_double(s, r.F(F_MIN + static_cast<int>(field - 2))))
        return false;
    } else {
      return false;   // packed_centroids (7) or unknown
    }
    last = field;
  }
  return true;
}

// a message that holds at most its field 1, once, length-delimited:
// HistogramValue {1: TDigest} and SetValue {1: bytes}
bool lone_bytes_field(Span s, Span& sub) {
  sub.p = sub.end = s.p;
  if (s.done()) return true;
  uint64_t field, wt;
  return read_tag(s, field, wt) && field == 1 && wt == WT_BYTES &&
         read_bytes(s, sub) && s.done();
}

// one metricpb.Metric of the plain shape into its row, or K_FALLBACK
int64_t plain_metric(const uint8_t* base, Span s, const Row& r,
                     Columns& c) {
  int64_t kind = K_NONE;
  const uint8_t* key_end = s.p;
  uint64_t last = 0, field, wt, v, f1, w1;
  Span sub, inner;
  const uint8_t* const key = s.p;
  r.I(I_KEY_OFF) = key - base;
  r.I(I_KEY_LEN) = r.I(I_A) = r.I(I_B) = 0;
  for (int k = 0; k < N_FLOATS; k++) r.F(k) = 0.0;
  while (!s.done()) {
    if (!read_tag(s, field, wt)) return K_FALLBACK;
    // number order, each once; `tags` (2) alone repeats
    if (field < last || (field == last && field != 2)) return K_FALLBACK;
    last = field;
    switch (field) {
      case 1: case 2:           // name, tags: the key's bytes
        if (wt != WT_BYTES || !read_bytes(s, sub)) return K_FALLBACK;
        key_end = s.p;
        break;
      case 3:                   // type: the key's last byte(s)
        if (wt != WT_VARINT || !read_varint(s, v)) return K_FALLBACK;
        key_end = s.p;
        break;
      case 4: case 5: case 6: case 7:   // the oneof: one member
        if (kind != K_NONE || wt != WT_BYTES || !read_bytes(s, sub))
          return K_FALLBACK;
        if (field == 4) {       // CounterValue {1: int64}
          kind = K_COUNTER;
          if (!sub.done()) {
            if (!read_tag(sub, f1, w1) || f1 != 1 ||
                w1 != WT_VARINT || !read_varint(sub, v) || !sub.done())
              return K_FALLBACK;
            r.I(I_A) = static_cast<int64_t>(v);
          }
        } else if (field == 5) {  // GaugeValue {1: double}
          kind = K_GAUGE;
          if (!sub.done() &&
              (!read_tag(sub, f1, w1) || f1 != 1 ||
               w1 != WT_FIXED64 || !read_double(sub, r.F(0)) || !sub.done()))
            return K_FALLBACK;
        } else if (field == 6) {
          kind = K_HISTOGRAM;
          r.I(I_A) = c.n;
          if (!lone_bytes_field(sub, inner) || !tdigest(inner, c, r))
            return K_FALLBACK;
          r.I(I_B) = c.n;
        } else {
          kind = K_SET;
          if (!lone_bytes_field(sub, inner)) return K_FALLBACK;
          r.I(I_A) = inner.p - base;
          r.I(I_B) = inner.end - inner.p;
        }
        break;
      case 8:                   // scope
        if (wt != WT_VARINT || !read_varint(s, v)) return K_FALLBACK;
        break;
      case 10:                  // hostname
        if (wt != WT_BYTES || !read_bytes(s, sub)) return K_FALLBACK;
        break;
      default:                  // status_check (9), unknown
        return K_FALLBACK;
    }
  }
  r.I(I_KEY_LEN) = key_end - key;
  return kind;
}

// ... and a metric that fell back leaves no centroid in the columns
int64_t metric(const uint8_t* base, Span s, const Row& r, Columns& c) {
  const int64_t first_centroid = c.n;
  const int64_t kind = plain_metric(base, s, r, c);
  if (kind == K_FALLBACK) c.n = first_centroid;
  return kind;
}

}  // namespace

extern "C" {

// Walk the `len` bytes of a serialized forwardrpc.MetricList and fill a
// row a metric: all of them where `positions` is null, in which case
// the list must hold exactly `n`; else the `n` whose positions in the
// list (ascending) `positions` names. `ints` is int64[5][n], a column
// each: kind, the span of the key's bytes (Metric fields 1-3 as they lie
// in `buf`: offset, length), and two more by kind: a histogram's
// [start, stop) into `means` / `weights`, a set's payload span (offset,
// length), a counter's value. `floats` is f64[5][n]: a histogram's min,
// max, sum, count, reciprocal_sum; a gauge's value first. A K_FALLBACK
// row holds nothing else and has left no centroid in the columns. Returns the
// centroids written (at most `cap`), or -1 where the list itself is not
// walkable or does not hold the metrics asked for: the caller then
// decodes the whole batch in Python.
int64_t vtpu_wire_decode(const uint8_t* buf, int64_t len,
                         const int64_t* positions, int64_t n,
                         int64_t* ints, double* floats,
                         float* means, float* weights, int64_t cap) {
  if (len < 0 || n < 0 || cap < 0) return -1;
  Span s{buf, buf + len};
  Columns c{means, weights, 0, cap};
  int64_t seen = 0, row = 0;
  uint64_t field, wt;
  Span sub;
  while (!s.done()) {
    if (!read_tag(s, field, wt) || field == 0) return -1;
    if (field != 1 || wt != WT_BYTES) {   // envelope, sketches, stamp
      if (!skip_field(s, wt)) return -1;
      continue;
    }
    if (!read_bytes(s, sub)) return -1;
    const int64_t at = seen++;
    if (positions ? (row >= n || positions[row] != at) : row >= n) {
      if (!positions) return -1;          // more metrics than rows
      continue;
    }
    const Row r{ints + row, floats + row, n};
    r.I(I_KIND) = metric(buf, sub, r, c);
    row++;
  }
  return row == n ? c.n : -1;
}

}  // extern "C"

// ---- the sender's pass ----

namespace {

enum : uint8_t { T_COUNTER = 0, T_GAUGE = 1, T_SET = 3 };   // metricpb.Type
enum : uint8_t { SCOPE_GLOBAL = 2 };                        // metricpb.Scope

inline int64_t varint_size(uint64_t v) {
  int64_t n = 1;
  while (v >= 0x80) { v >>= 7; n++; }
  return n;
}

// a length-delimited field of `n` payload bytes under a one-byte tag
inline int64_t ld_size(int64_t n) { return 1 + varint_size(n) + n; }

inline uint8_t* put_varint(uint8_t* p, uint64_t v) {
  while (v >= 0x80) { *p++ = static_cast<uint8_t>(v) | 0x80; v >>= 7; }
  *p++ = static_cast<uint8_t>(v);
  return p;
}

inline uint8_t* put_ld(uint8_t* p, uint8_t tag, int64_t n) {
  *p++ = tag;
  return put_varint(p, static_cast<uint64_t>(n));
}

inline uint8_t* put_bytes(uint8_t* p, uint8_t tag, const uint8_t* b,
                          int64_t n) {
  p = put_ld(p, tag, n);
  std::memcpy(p, b, n);
  return p + n;
}

// proto3 leaves out a double whose bits are all zero: 0.0, not -0.0
inline bool present(double v) {
  uint64_t bits;
  std::memcpy(&bits, &v, 8);
  return bits != 0;
}

inline uint8_t* put_double(uint8_t* p, uint8_t tag, double v) {
  if (!present(v)) return p;
  *p++ = tag;
  std::memcpy(p, &v, 8);
  return p + 8;
}

// `tags` (2), one a comma-separated piece of the key's joined tags,
// an empty piece too; none where the key has no tags at all
int64_t tags_size(const uint8_t* t, int64_t n) {
  if (n == 0) return 0;
  int64_t size = 0, piece = 0;
  for (int64_t i = 0; i <= n; i++) {
    if (i == n || t[i] == ',') { size += ld_size(piece); piece = 0; }
    else piece++;
  }
  return size;
}

uint8_t* put_tags(uint8_t* p, const uint8_t* t, int64_t n) {
  if (n == 0) return p;
  int64_t start = 0;
  for (int64_t i = 0; i <= n; i++) {
    if (i == n || t[i] == ',') {
      p = put_bytes(p, 0x12, t + start, i - start);
      start = i + 1;
    }
  }
  return p;
}

// metricpb.TDigest: a Centroid a weight above zero, then the five
// exact statistics
template <typename T>
int64_t tdigest_size(const T* means, const T* weights, int64_t k,
                     const double* stats) {
  int64_t size = 0;
  for (int64_t j = 0; j < k; j++)
    if (weights[j] > 0)
      size += 2 + 9 + (present(static_cast<double>(means[j])) ? 9 : 0);
  for (int s = 0; s < 5; s++) size += present(stats[s]) ? 9 : 0;
  return size;
}

template <typename T>
uint8_t* put_tdigest(uint8_t* p, const T* means, const T* weights,
                     int64_t k, const double* stats) {
  for (int64_t j = 0; j < k; j++) {
    if (!(weights[j] > 0)) continue;
    const double mean = static_cast<double>(means[j]);
    p = put_ld(p, 0x0a, 9 + (present(mean) ? 9 : 0));
    p = put_double(p, 0x09, mean);
    p = put_double(p, 0x11, static_cast<double>(weights[j]));
  }
  for (int s = 0; s < 5; s++)
    p = put_double(p, static_cast<uint8_t>(0x11 + 8 * s), stats[s]);
  return p;
}

// the pass, for centroids held as T (vtpu_wire_encode, below)
template <typename T>
int64_t encode(const int64_t* counts, const uint8_t* names,
               const int64_t* name_off, const uint8_t* tags,
               const int64_t* tag_off, const uint8_t* types,
               const int64_t* cent_off, const T* means, const T* weights,
               const double* stats, const uint8_t* sets,
               const int64_t* set_off, const double* counters,
               const double* gauges, uint8_t* out, int64_t cap,
               int64_t* off, int64_t* body) {
  const int64_t n_h = counts[0], n_s = counts[1], n_c = counts[2];
  const int64_t n = n_h + n_s + n_c + counts[3];
  uint8_t* p = out;
  for (int64_t i = 0; i < n; i++) {
    off[i] = p - out;
    const int64_t nl = name_off[i + 1] - name_off[i];
    const int64_t tl = tag_off[i + 1] - tag_off[i];
    if (nl < 0 || tl < 0) return -1;
    const uint8_t* name = names + name_off[i];
    const uint8_t* tag = tags + tag_off[i];
    // the member of the oneof: its field's tag, its payload's size
    // (`value`), and what the payload is made from
    uint8_t type, member;
    int64_t value, inner = 0, k = 0, j = i;
    const T* m = means;
    const T* w = weights;
    if (j < n_h) {                     // HistogramValue {1: TDigest}
      type = types[j];
      member = 0x32;
      k = cent_off[j + 1] - cent_off[j];
      if (k < 0) return -1;
      m += cent_off[j];
      w += cent_off[j];
      inner = tdigest_size(m, w, k, stats + 5 * j);
      value = ld_size(inner);
    } else if ((j -= n_h) < n_s) {     // SetValue {1: bytes}
      type = T_SET;
      member = 0x3a;
      inner = set_off[j + 1] - set_off[j];
      if (inner < 0) return -1;
      value = inner ? ld_size(inner) : 0;
    } else if ((j -= n_s) < n_c) {     // CounterValue {1: int64}
      type = T_COUNTER;
      member = 0x22;
      const double v = counters[j];
      if (!(v >= -9223372036854775808.0 && v < 9223372036854775808.0))
        return -1;
      inner = static_cast<int64_t>(v);
      value = inner ? 1 + varint_size(static_cast<uint64_t>(inner)) : 0;
    } else {                           // GaugeValue {1: double}
      j -= n_c;
      type = T_GAUGE;
      member = 0x2a;
      value = present(gauges[j]) ? 9 : 0;
    }
    const int64_t size = (nl ? ld_size(nl) : 0) + tags_size(tag, tl) +
                         (type ? 2 : 0) + ld_size(value) + 2;
    if (ld_size(size) > cap - (p - out)) return -1;
    body[i] = size;
    p = put_ld(p, 0x0a, size);
    if (nl) p = put_bytes(p, 0x0a, name, nl);
    p = put_tags(p, tag, tl);
    if (type) { *p++ = 0x18; *p++ = type; }
    p = put_ld(p, member, value);
    if (member == 0x32) {
      p = put_tdigest(put_ld(p, 0x0a, inner), m, w, k, stats + 5 * j);
    } else if (member == 0x3a) {
      if (inner) p = put_bytes(p, 0x0a, sets + set_off[j], inner);
    } else if (member == 0x22) {
      if (inner) p = put_varint(put_varint(p, 0x08), static_cast<uint64_t>(inner));
    } else {
      p = put_double(p, 0x09, gauges[j]);
    }
    *p++ = 0x40;
    *p++ = SCOPE_GLOBAL;
  }
  off[n] = p - out;
  return p - out;
}

}  // namespace

extern "C" {

// Write an export's sketches as the `metrics` of a forwardrpc.MetricList:
// each a length-delimited field 1 whose payload is the metricpb.Metric
// that export_to_metrics builds for it, in its order: `counts[0]`
// histograms, `counts[1]` sets, `counts[2]` counters, `counts[3]`
// gauges, n in all. A key's name is names[name_off[i] : name_off[i + 1]]
// and its joined tags the same span of `tags` (UTF-8; every offset array
// starts at 0 and never falls). Histogram j has the wire type `types[j]`,
// the centroids [cent_off[j], cent_off[j + 1]) of `means` / `weights`
// (f64 where `wide`, else f32; one with no weight above zero is left
// out) and the statistics stats[j][0..5): min, max, sum, count,
// reciprocal_sum. Set j's payload is sets[set_off[j] : set_off[j + 1]];
// counter j's value `counters[j]`, rounded by the caller, is written as
// the int64 it is; gauge j's is `gauges[j]`. Metric i is written at
// out[off[i] : off[i + 1]] and body[i] is its Metric's size (what
// ByteSize() says). Returns the bytes written, or -1 for an export the
// pass will not write: a counter that is no int64 (not finite, or past
// 2^63), a metric that does not fit what is left of `cap`, an offset
// array that falls.
int64_t vtpu_wire_encode(const int64_t* counts, const uint8_t* names,
                         const int64_t* name_off, const uint8_t* tags,
                         const int64_t* tag_off, const uint8_t* types,
                         const int64_t* cent_off, const void* means,
                         const void* weights, const double* stats,
                         const uint8_t* sets, const int64_t* set_off,
                         const double* counters, const double* gauges,
                         int64_t wide, uint8_t* out, int64_t cap,
                         int64_t* off, int64_t* body) {
  if (wide)
    return encode(counts, names, name_off, tags, tag_off, types, cent_off,
                  static_cast<const double*>(means),
                  static_cast<const double*>(weights), stats, sets, set_off,
                  counters, gauges, out, cap, off, body);
  return encode(counts, names, name_off, tags, tag_off, types, cent_off,
                static_cast<const float*>(means),
                static_cast<const float*>(weights), stats, sets, set_off,
                counters, gauges, out, cap, off, body);
}

}  // extern "C"
