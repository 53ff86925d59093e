// C predict API — standalone native inference over an exported
// `-symbol.json` + `.params` pair, no Python dependency
// (ref: src/c_api/c_predict_api.cc MXPredCreate/SetInput/Forward/
// GetOutput/Free; the reference drives the full C++ runtime, here a
// self-contained CPU graph interpreter covers the deployment path the
// reference's amalgamation/mobile builds serve).
//
// Supported ops: Convolution, FullyConnected, BatchNorm (inference, with
// its fused act_type), Activation, Pooling, Flatten, Reshape,
// elemwise/broadcast add/mul/sub/div, scalar ops, Concat, softmax,
// log_softmax, Dropout (identity), LeakyReLU (leaky/elu/gelu), Embedding,
// LayerNorm, the fused epilogues Gluon's Dense and the resnet-v1 blocks
// export (_contrib_matmul_epilogue = act(y + bias), _contrib_conv_epilogue
// = act(x + res); contrib/onnx/mx2onnx.py lowers them the same way),
// fused self/cross attention, transpose, batch_dot, slice/slice_like,
// expand_dims, squeeze — the exported-model op sets of the model zoo's
// image classifiers (LeNet/MLP/ResNet/VGG) AND the transformer family
// (BERT encoder, Sockeye-style NMT transformer).
//
// Build: part of libmxtpu.so (see Makefile). C ABI mirrors the
// reference's signatures.

#include <algorithm>
#include <cctype>
#include <climits>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <map>
#include <memory>
#include <stdexcept>
#include <string>
#include <vector>

namespace predict {

// ---------------------------------------------------------------------------
// minimal JSON parser (objects, arrays, strings, numbers, bool, null)
// ---------------------------------------------------------------------------
struct JValue {
  enum Kind { OBJ, ARR, STR, NUM, BOOL, NUL } kind = NUL;
  std::map<std::string, JValue> obj;
  std::vector<JValue> arr;
  std::string str;
  double num = 0;
  bool b = false;
  const JValue& operator[](const std::string& k) const {
    static JValue nul;
    auto it = obj.find(k);
    return it == obj.end() ? nul : it->second;
  }
};

struct JParser {
  const char* p;
  const char* end;
  explicit JParser(const std::string& s) : p(s.data()), end(s.data() + s.size()) {}
  void skip() { while (p < end && std::isspace((unsigned char)*p)) ++p; }
  [[noreturn]] void fail(const char* msg) {
    throw std::runtime_error(std::string("json: ") + msg);
  }
  JValue parse() { skip(); return value(); }
  JValue value() {
    skip();
    if (p >= end) fail("eof");
    switch (*p) {
      case '{': return object();
      case '[': return array();
      case '"': { JValue v; v.kind = JValue::STR; v.str = string(); return v; }
      case 't': p += 4; { JValue v; v.kind = JValue::BOOL; v.b = true; return v; }
      case 'f': p += 5; { JValue v; v.kind = JValue::BOOL; v.b = false; return v; }
      case 'n': p += 4; return JValue{};
      default: return number();
    }
  }
  JValue object() {
    JValue v; v.kind = JValue::OBJ; ++p;  // '{'
    skip();
    if (p < end && *p == '}') { ++p; return v; }
    while (true) {
      skip();
      std::string key = string();
      skip();
      if (p >= end || *p != ':') fail("expected :");
      ++p;
      v.obj[key] = value();
      skip();
      if (p < end && *p == ',') { ++p; continue; }
      if (p < end && *p == '}') { ++p; break; }
      fail("expected , or }");
    }
    return v;
  }
  JValue array() {
    JValue v; v.kind = JValue::ARR; ++p;  // '['
    skip();
    if (p < end && *p == ']') { ++p; return v; }
    while (true) {
      v.arr.push_back(value());
      skip();
      if (p < end && *p == ',') { ++p; continue; }
      if (p < end && *p == ']') { ++p; break; }
      fail("expected , or ]");
    }
    return v;
  }
  std::string string() {
    if (*p != '"') fail("expected string");
    ++p;
    std::string out;
    while (p < end && *p != '"') {
      if (*p == '\\' && p + 1 < end) {
        ++p;
        switch (*p) {
          case 'n': out += '\n'; break;
          case 't': out += '\t'; break;
          case 'r': out += '\r'; break;
          case 'u': p += 4; out += '?'; break;  // no unicode in our files
          default: out += *p;
        }
      } else {
        out += *p;
      }
      ++p;
    }
    ++p;
    return out;
  }
  JValue number() {
    char* np = nullptr;
    JValue v; v.kind = JValue::NUM;
    v.num = std::strtod(p, &np);
    if (np == p) fail("bad number");
    p = np;
    return v;
  }
};

// ---------------------------------------------------------------------------
// attr parsing (python-repr strings: "(3, 3)", "64", "True", "relu")
// ---------------------------------------------------------------------------
static std::vector<long> parse_tuple(const std::string& s) {
  std::vector<long> out;
  long cur = 0;
  bool in_num = false, neg = false;
  for (char c : s) {
    if (std::isdigit((unsigned char)c)) { cur = cur * 10 + (c - '0'); in_num = true; }
    else if (c == '-') { neg = true; }
    else if (in_num) { out.push_back(neg ? -cur : cur); cur = 0; in_num = false; neg = false; }
  }
  if (in_num) out.push_back(neg ? -cur : cur);
  return out;
}
static long parse_int(const std::string& s, long dflt) {
  if (s.empty()) return dflt;
  try { return std::stol(s); } catch (...) { return dflt; }
}
static double parse_float(const std::string& s, double dflt) {
  if (s.empty()) return dflt;
  try { return std::stod(s); } catch (...) { return dflt; }
}
static bool parse_bool(const std::string& s, bool dflt) {
  if (s == "True" || s == "true" || s == "1") return true;
  if (s == "False" || s == "false" || s == "0") return false;
  return dflt;
}

// ---------------------------------------------------------------------------
// tensors
// ---------------------------------------------------------------------------
struct Tensor {
  std::vector<long> shape;
  std::vector<float> data;
  long size() const {
    long n = 1;
    for (long s : shape) n *= s;
    return n;
  }
  void alloc() { data.assign(size(), 0.f); }
};

// ---------------------------------------------------------------------------
// .params reader (format: ndarray.py save — list magic, ndarray records,
// then names; names carry arg:/aux: prefixes). Format flag word 1 = the
// crash-consistent v3 container (docs/checkpointing.md): a CRC32 after
// every entry and a 24-byte <body_len, names_crc, reserved, magic>
// footer. This reader checks the footer's structural claim (body length
// vs buffer size — catches truncation up front) and skips the CRCs
// (the Python loader owns checksum verification; no zlib dependency
// here). Flag 0 = the reference-era layout, unchanged.
// ---------------------------------------------------------------------------
struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  Reader(const void* buf, size_t n)
      : p((const uint8_t*)buf), end((const uint8_t*)buf + n) {}
  template <typename T> T get() {
    if (p + sizeof(T) > end) throw std::runtime_error("params: truncated");
    T v;
    std::memcpy(&v, p, sizeof(T));
    p += sizeof(T);
    return v;
  }
};

static const uint64_t kParamsFooterMagic = 0x4D58545043524333ULL;
static const size_t kParamsFooterBytes = 24;

static std::map<std::string, Tensor> load_params(const void* buf, size_t n) {
  Reader r(buf, n);
  uint64_t magic = r.get<uint64_t>();
  if (magic != 0x112) throw std::runtime_error("params: bad list magic");
  uint64_t fmt = r.get<uint64_t>();  // 0 = legacy, 1 = CRC + footer
  if (fmt > 1)
    throw std::runtime_error("params: unsupported format flag " +
                             std::to_string(fmt));
  bool crc = fmt == 1;
  if (crc) {
    if (n < 16 + kParamsFooterBytes)
      throw std::runtime_error("params: truncated (no footer)");
    const uint8_t* foot = (const uint8_t*)buf + n - kParamsFooterBytes;
    uint64_t body_len, foot_magic;
    std::memcpy(&body_len, foot, 8);
    std::memcpy(&foot_magic, foot + 16, 8);
    if (foot_magic != kParamsFooterMagic || body_len != n - kParamsFooterBytes)
      throw std::runtime_error("params: footer missing or inconsistent "
                               "(interrupted save?)");
    r.end -= kParamsFooterBytes;  // names stop before the footer
  }
  uint64_t count = r.get<uint64_t>();
  std::vector<Tensor> arrays(count);
  for (uint64_t i = 0; i < count; ++i) {
    uint32_t nd_magic = r.get<uint32_t>();
    if (nd_magic != 0xF993FAC9) throw std::runtime_error("params: bad nd magic");
    uint32_t ndim = r.get<uint32_t>();
    Tensor t;
    for (uint32_t d = 0; d < ndim; ++d) t.shape.push_back((long)r.get<int64_t>());
    r.get<int32_t>();  // dev_type
    r.get<int32_t>();  // dev_id
    int32_t dtype = r.get<int32_t>();
    long sz = t.size();
    t.data.resize(sz);
    if (dtype == 0) {  // float32
      for (long j = 0; j < sz; ++j) t.data[j] = r.get<float>();
    } else if (dtype == 1) {  // float64
      for (long j = 0; j < sz; ++j) t.data[j] = (float)r.get<double>();
    } else if (dtype == 6) {  // int64  (code table: ndarray.py _DTYPE_CODE)
      for (long j = 0; j < sz; ++j) t.data[j] = (float)r.get<int64_t>();
    } else if (dtype == 4) {  // int32
      for (long j = 0; j < sz; ++j) t.data[j] = (float)r.get<int32_t>();
    } else {
      throw std::runtime_error("params: unsupported dtype code " +
                               std::to_string(dtype));
    }
    if (crc) r.get<uint32_t>();  // per-entry CRC32 (verified Python-side)
    arrays[i] = std::move(t);
  }
  uint64_t n_names = r.get<uint64_t>();
  if (n_names > count)
    throw std::runtime_error("params: more names than arrays");
  std::map<std::string, Tensor> out;
  for (uint64_t i = 0; i < n_names; ++i) {
    uint64_t len = r.get<uint64_t>();
    if (len > (size_t)(r.end - r.p))   // no pointer arithmetic: huge len
      throw std::runtime_error("params: truncated name");
    std::string name((const char*)r.p, len);
    r.p += len;
    // strip arg:/aux: prefixes
    auto pos = name.find(':');
    if (pos != std::string::npos) name = name.substr(pos + 1);
    out[name] = std::move(arrays[i]);
  }
  return out;
}

// ---------------------------------------------------------------------------
// op kernels (NCHW, fp32, plain loops — deployment-correctness path)
// ---------------------------------------------------------------------------
static void conv2d(const Tensor& x, const Tensor& w, const Tensor* bias,
                   const std::vector<long>& stride, const std::vector<long>& pad,
                   const std::vector<long>& dilate, long groups, Tensor& out) {
  long N = x.shape[0], C = x.shape[1], H = x.shape[2], W = x.shape[3];
  long O = w.shape[0], KH = w.shape[2], KW = w.shape[3];
  long SH = stride[0], SW = stride[1], PH = pad[0], PW = pad[1];
  long DH = dilate[0], DW = dilate[1];
  long OH = (H + 2 * PH - (DH * (KH - 1) + 1)) / SH + 1;
  long OW = (W + 2 * PW - (DW * (KW - 1) + 1)) / SW + 1;
  long Cg = C / groups, Og = O / groups;
  out.shape = {N, O, OH, OW};
  out.alloc();
  for (long n = 0; n < N; ++n)
    for (long o = 0; o < O; ++o) {
      long g = o / Og;
      for (long oy = 0; oy < OH; ++oy)
        for (long ox = 0; ox < OW; ++ox) {
          float acc = bias ? bias->data[o] : 0.f;
          for (long c = 0; c < Cg; ++c)
            for (long ky = 0; ky < KH; ++ky) {
              long iy = oy * SH - PH + ky * DH;
              if (iy < 0 || iy >= H) continue;
              for (long kx = 0; kx < KW; ++kx) {
                long ix = ox * SW - PW + kx * DW;
                if (ix < 0 || ix >= W) continue;
                acc += x.data[((n * C + g * Cg + c) * H + iy) * W + ix] *
                       w.data[((o * Cg + c) * KH + ky) * KW + kx];
              }
            }
          out.data[((n * O + o) * OH + oy) * OW + ox] = acc;
        }
    }
}

static void fully_connected(const Tensor& x, const Tensor& w,
                            const Tensor* bias, bool flatten, Tensor& out) {
  long K = w.shape[1], O = w.shape[0];
  long N;
  std::vector<long> lead;
  if (flatten || x.shape.size() == 2) {
    N = x.shape[0];
    lead = {N};
  } else {
    N = x.size() / x.shape.back();
    lead.assign(x.shape.begin(), x.shape.end() - 1);
  }
  out.shape = lead;
  out.shape.push_back(O);
  out.alloc();
  for (long n = 0; n < N; ++n)
    for (long o = 0; o < O; ++o) {
      float acc = bias ? bias->data[o] : 0.f;
      const float* xr = &x.data[n * K];
      const float* wr = &w.data[o * K];
      for (long k = 0; k < K; ++k) acc += xr[k] * wr[k];
      out.data[n * O + o] = acc;
    }
}

static void batchnorm(const Tensor& x, const Tensor& gamma, const Tensor& beta,
                      const Tensor& mean, const Tensor& var, double eps,
                      bool fix_gamma, Tensor& out) {
  out.shape = x.shape;
  out.alloc();
  long C = x.shape.size() > 1 ? x.shape[1] : x.shape[0];
  long inner = 1;
  for (size_t i = 2; i < x.shape.size(); ++i) inner *= x.shape[i];
  long N = x.shape[0];
  for (long c = 0; c < C; ++c) {
    float g = fix_gamma ? 1.f : gamma.data[c];
    float inv = 1.f / std::sqrt(var.data[c] + (float)eps);
    float scale = g * inv;
    float offset = beta.data[c] - mean.data[c] * scale;
    for (long n = 0; n < N; ++n) {
      float* po = &out.data[(n * C + c) * inner];
      const float* px = &x.data[(n * C + c) * inner];
      for (long i = 0; i < inner; ++i) po[i] = px[i] * scale + offset;
    }
  }
}

static void pooling(const Tensor& x, const std::string& type, bool global_pool,
                    const std::vector<long>& kernel,
                    const std::vector<long>& stride,
                    const std::vector<long>& pad, bool ceil_mode,
                    bool count_include_pad, Tensor& out) {
  long N = x.shape[0], C = x.shape[1], H = x.shape[2], W = x.shape[3];
  if (global_pool) {
    out.shape = {N, C, 1, 1};
    out.alloc();
    for (long n = 0; n < N; ++n)
      for (long c = 0; c < C; ++c) {
        const float* px = &x.data[(n * C + c) * H * W];
        float acc = type == "max" ? -1e30f : 0.f;
        for (long i = 0; i < H * W; ++i)
          acc = type == "max" ? std::max(acc, px[i]) : acc + px[i];
        out.data[(n * C + c)] = type == "max" ? acc : acc / (float)(H * W);
      }
    return;
  }
  long KH = kernel[0], KW = kernel[1];
  long SH = stride[0], SW = stride[1], PH = pad[0], PW = pad[1];
  auto osize = [&](long in, long k, long s, long p) {
    double v = (double)(in + 2 * p - k) / s + 1;
    return (long)(ceil_mode ? std::ceil(v) : std::floor(v));
  };
  long OH = osize(H, KH, SH, PH), OW = osize(W, KW, SW, PW);
  out.shape = {N, C, OH, OW};
  out.alloc();
  for (long n = 0; n < N; ++n)
    for (long c = 0; c < C; ++c)
      for (long oy = 0; oy < OH; ++oy)
        for (long ox = 0; ox < OW; ++ox) {
          float acc = type == "max" ? -1e30f : 0.f;
          long cnt = 0;
          for (long ky = 0; ky < KH; ++ky) {
            long iy = oy * SH - PH + ky;
            if (iy < 0 || iy >= H) continue;
            for (long kx = 0; kx < KW; ++kx) {
              long ix = ox * SW - PW + kx;
              if (ix < 0 || ix >= W) continue;
              float v = x.data[((n * C + c) * H + iy) * W + ix];
              acc = type == "max" ? std::max(acc, v) : acc + v;
              ++cnt;
            }
          }
          if (type != "max")
            acc /= (float)(count_include_pad ? KH * KW : std::max(cnt, 1L));
          out.data[((n * C + c) * OH + oy) * OW + ox] = acc;
        }
}

static void softmax_rows(Tensor& t) {
  long C = t.shape.back();
  long rows = t.size() / C;
  for (long r = 0; r < rows; ++r) {
    float* p = &t.data[r * C];
    float m = *std::max_element(p, p + C);
    double s = 0;
    for (long c = 0; c < C; ++c) { p[c] = std::exp(p[c] - m); s += p[c]; }
    for (long c = 0; c < C; ++c) p[c] = (float)(p[c] / s);
  }
}

// ---- transformer-family kernels (exported BERT / NMT graphs) --------------

static void embedding(const Tensor& idx, const Tensor& w, Tensor& out) {
  long V = w.shape[0], U = w.shape[1];
  out.shape = idx.shape;
  out.shape.push_back(U);
  out.alloc();
  for (long i = 0; i < idx.size(); ++i) {
    long row = (long)std::lround(idx.data[i]);
    if (row < 0 || row >= V)
      throw std::runtime_error("Embedding index out of range");
    std::memcpy(&out.data[i * U], &w.data[row * U], U * sizeof(float));
  }
}

static void layernorm(const Tensor& x, const Tensor& gamma,
                      const Tensor& beta, double eps, long axis, Tensor& out) {
  long nd = (long)x.shape.size();
  if (axis < 0) axis += nd;
  if (axis != nd - 1)
    throw std::runtime_error("LayerNorm: only last-axis supported");
  long C = x.shape.back();
  long rows = x.size() / C;
  out.shape = x.shape;
  out.alloc();
  for (long r = 0; r < rows; ++r) {
    const float* px = &x.data[r * C];
    float* po = &out.data[r * C];
    double m = 0, v = 0;
    for (long c = 0; c < C; ++c) m += px[c];
    m /= C;
    for (long c = 0; c < C; ++c) { double d = px[c] - m; v += d * d; }
    v /= C;
    float inv = 1.f / std::sqrt((float)v + (float)eps);
    for (long c = 0; c < C; ++c)
      po[c] = (float)((px[c] - m) * inv) * gamma.data[c] + beta.data[c];
  }
}

// softmax over the last axis of a (rows, C) view of scores
static void softmax_inplace(float* p, long C) {
  float m = *std::max_element(p, p + C);
  double s = 0;
  for (long c = 0; c < C; ++c) { p[c] = std::exp(p[c] - m); s += p[c]; }
  for (long c = 0; c < C; ++c) p[c] = (float)(p[c] / s);
}

// q (B,Sq,H,D) laid flat out of proj rows; generic core shared by the fused
// self/cross attention ops (ref: the Python ops' einsum formulation,
// mxnet_tpu/ops/contrib.py _fused_self_attention/_fused_cross_attention)
static void attention_core(const float* q, const float* k, const float* v,
                           long B, long Sq, long Sk, long H, long D,
                           bool causal, float* outp) {
  float scale = 1.f / std::sqrt((float)D);
  std::vector<float> row(Sk);
  for (long b = 0; b < B; ++b)
    for (long h = 0; h < H; ++h)
      for (long i = 0; i < Sq; ++i) {
        const float* qi = &q[((b * Sq + i) * H + h) * D];
        for (long j = 0; j < Sk; ++j) {
          if (causal && j > i) { row[j] = -1e30f; continue; }
          const float* kj = &k[((b * Sk + j) * H + h) * D];
          float acc = 0;
          for (long d = 0; d < D; ++d) acc += qi[d] * kj[d];
          row[j] = acc * scale;
        }
        softmax_inplace(row.data(), Sk);
        float* oi = &outp[((b * Sq + i) * H + h) * D];
        for (long d = 0; d < D; ++d) oi[d] = 0.f;
        for (long j = 0; j < Sk; ++j) {
          const float* vj = &v[((b * Sk + j) * H + h) * D];
          float a = row[j];
          for (long d = 0; d < D; ++d) oi[d] += a * vj[d];
        }
      }
}

static void self_attention(const Tensor& qkv, long heads, bool causal,
                           Tensor& out) {
  long B = qkv.shape[0], S = qkv.shape[1], C = qkv.shape[2] / 3;
  long D = C / heads;
  // split (B,S,3C) rows into contiguous q/k/v in (B,S,H,D) flat layout
  std::vector<float> q(B * S * C), k(B * S * C), v(B * S * C);
  for (long r = 0; r < B * S; ++r) {
    const float* src = &qkv.data[r * 3 * C];
    std::memcpy(&q[r * C], src, C * sizeof(float));
    std::memcpy(&k[r * C], src + C, C * sizeof(float));
    std::memcpy(&v[r * C], src + 2 * C, C * sizeof(float));
  }
  out.shape = {B, S, C};
  out.alloc();
  attention_core(q.data(), k.data(), v.data(), B, S, S, heads, D, causal,
                 out.data.data());
}

static void cross_attention(const Tensor& qt, const Tensor& kv, long heads,
                            Tensor& out) {
  long B = qt.shape[0], Sq = qt.shape[1], C = qt.shape[2];
  long Sk = kv.shape[1], D = C / heads;
  std::vector<float> k(B * Sk * C), v(B * Sk * C);
  for (long r = 0; r < B * Sk; ++r) {
    const float* src = &kv.data[r * 2 * C];
    std::memcpy(&k[r * C], src, C * sizeof(float));
    std::memcpy(&v[r * C], src + C, C * sizeof(float));
  }
  out.shape = {B, Sq, C};
  out.alloc();
  attention_core(qt.data.data(), k.data(), v.data(), B, Sq, Sk, heads, D,
                 false, out.data.data());
}

static void transpose_nd(const Tensor& x, const std::vector<long>& axes,
                         Tensor& out) {
  long nd = (long)x.shape.size();
  std::vector<long> ax = axes;
  if (ax.empty())
    for (long i = nd - 1; i >= 0; --i) ax.push_back(i);
  out.shape.resize(nd);
  for (long i = 0; i < nd; ++i) out.shape[i] = x.shape[ax[i]];
  out.alloc();
  std::vector<long> xstride(nd, 1), ostride(nd, 1);
  for (long i = nd - 2; i >= 0; --i)
    xstride[i] = xstride[i + 1] * x.shape[i + 1];
  for (long i = nd - 2; i >= 0; --i)
    ostride[i] = ostride[i + 1] * out.shape[i + 1];
  std::vector<long> oidx(nd, 0);
  for (long o = 0; o < out.size(); ++o) {
    long rem = o, xoff = 0;
    for (long i = 0; i < nd; ++i) {
      long c = rem / ostride[i];
      rem %= ostride[i];
      xoff += c * xstride[ax[i]];
    }
    out.data[o] = x.data[xoff];
  }
}

static void batch_dot(const Tensor& a, const Tensor& b, bool ta, bool tb,
                      Tensor& out) {
  // (B.., M, K) x (B.., K, N); leading batch dims must match
  long nd = (long)a.shape.size();
  long M = ta ? a.shape[nd - 1] : a.shape[nd - 2];
  long K = ta ? a.shape[nd - 2] : a.shape[nd - 1];
  long N = tb ? b.shape[nd - 2] : b.shape[nd - 1];
  long batch = 1;
  for (long i = 0; i < nd - 2; ++i) batch *= a.shape[i];
  out.shape.assign(a.shape.begin(), a.shape.end() - 2);
  out.shape.push_back(M);
  out.shape.push_back(N);
  out.alloc();
  long as = M * K, bs = K * N;
  for (long g = 0; g < batch; ++g)
    for (long m = 0; m < M; ++m)
      for (long n2 = 0; n2 < N; ++n2) {
        float acc = 0;
        for (long kk = 0; kk < K; ++kk) {
          float av = ta ? a.data[g * as + kk * M + m]
                        : a.data[g * as + m * K + kk];
          float bv = tb ? b.data[g * bs + n2 * K + kk]
                        : b.data[g * bs + kk * N + n2];
          acc += av * bv;
        }
        out.data[(g * M + m) * N + n2] = acc;
      }
}

// numpy-style broadcast binary: op 0 add, 1 mul, 2 sub, 3 div
static void broadcast_binary(const Tensor& a, const Tensor& b, int op,
                             Tensor& out) {
  long nd = (long)std::max(a.shape.size(), b.shape.size());
  std::vector<long> sa(nd, 1), sb(nd, 1);
  std::copy(a.shape.begin(), a.shape.end(),
            sa.begin() + (nd - a.shape.size()));
  std::copy(b.shape.begin(), b.shape.end(),
            sb.begin() + (nd - b.shape.size()));
  out.shape.resize(nd);
  for (long i = 0; i < nd; ++i) {
    if (sa[i] != sb[i] && sa[i] != 1 && sb[i] != 1)
      throw std::runtime_error("broadcast shape mismatch");
    out.shape[i] = std::max(sa[i], sb[i]);
  }
  out.alloc();
  std::vector<long> so(nd, 1), ca(nd, 1), cb(nd, 1);
  for (long i = nd - 2; i >= 0; --i) {
    ca[i] = ca[i + 1] * sa[i + 1];
    cb[i] = cb[i + 1] * sb[i + 1];
    so[i] = so[i + 1] * out.shape[i + 1];
  }
  for (long o = 0; o < out.size(); ++o) {
    long rem = o, ia = 0, ib = 0;
    for (long i = 0; i < nd; ++i) {
      long c = rem / so[i];
      rem %= so[i];
      ia += (sa[i] == 1 ? 0 : c) * ca[i];
      ib += (sb[i] == 1 ? 0 : c) * cb[i];
    }
    float x = a.data[ia], y = b.data[ib];
    out.data[o] = op == 0 ? x + y : op == 1 ? x * y
                  : op == 2 ? x - y : x / y;
  }
}

// One activation by its MXNet name, in place: the act_types of Activation,
// of LeakyReLU (`slope`) and of the fused epilogue ops, whose absent or
// "None" act_type is the identity.
static void apply_activation(Tensor& t, const std::string& act,
                             float slope = 0.25f) {
  if (act.empty() || act == "None" || act == "identity") return;
  static const char* const names[] = {"relu", "sigmoid", "tanh", "softrelu",
                                      "gelu", "leaky", "elu"};
  size_t kind = 0;
  while (kind < 7 && act != names[kind]) ++kind;
  if (kind == 7) throw std::runtime_error("predict: unknown act_type " + act);
  for (float& v : t.data) {
    switch (kind) {
      case 0: v = std::max(v, 0.f); break;
      case 1: v = 1.f / (1.f + std::exp(-v)); break;
      case 2: v = std::tanh(v); break;
      case 3: v = std::log1p(std::exp(v)); break;
      case 4:   // exact erf form, like jax.nn.gelu
        v = 0.5f * v * (1.f + std::erf(v * 0.70710678f)); break;
      case 5: v = v > 0 ? v : slope * v; break;
      default: v = v > 0 ? v : slope * std::expm1(v);   // elu
    }
  }
}

// tuple parser that keeps None entries as LONG_MIN sentinels (for slice)
static const long kNone = LONG_MIN;
static std::vector<long> parse_tuple_opt(const std::string& s) {
  std::vector<long> out;
  long cur = 0;
  bool in_num = false, neg = false;
  for (size_t i = 0; i < s.size(); ++i) {
    char c = s[i];
    if (c == 'N') { out.push_back(kNone); }
    else if (std::isdigit((unsigned char)c)) {
      cur = cur * 10 + (c - '0');
      in_num = true;
    } else if (c == '-') {
      neg = true;
    } else if (in_num) {
      out.push_back(neg ? -cur : cur);
      cur = 0; in_num = false; neg = false;
    }
  }
  if (in_num) out.push_back(neg ? -cur : cur);
  return out;
}

static void slice_ranges(const Tensor& x, const std::vector<long>& begin,
                         const std::vector<long>& end, Tensor& out) {
  long nd = (long)x.shape.size();
  std::vector<long> b(nd, 0), e(x.shape);
  for (size_t i = 0; i < begin.size() && (long)i < nd; ++i) {
    if (begin[i] != kNone)
      b[i] = begin[i] < 0 ? begin[i] + x.shape[i] : begin[i];
    if (i < end.size() && end[i] != kNone)
      e[i] = end[i] < 0 ? end[i] + x.shape[i] : std::min(end[i], x.shape[i]);
  }
  out.shape.resize(nd);
  for (long i = 0; i < nd; ++i) out.shape[i] = e[i] - b[i];
  out.alloc();
  std::vector<long> xs(nd, 1), os(nd, 1);
  for (long i = nd - 2; i >= 0; --i) {
    xs[i] = xs[i + 1] * x.shape[i + 1];
    os[i] = os[i + 1] * out.shape[i + 1];
  }
  for (long o = 0; o < out.size(); ++o) {
    long rem = o, xoff = 0;
    for (long i = 0; i < nd; ++i) {
      long c = rem / os[i];
      rem %= os[i];
      xoff += (c + b[i]) * xs[i];
    }
    out.data[o] = x.data[xoff];
  }
}

// ---------------------------------------------------------------------------
// the graph executor
// ---------------------------------------------------------------------------
struct Node {
  std::string op, name;
  std::map<std::string, std::string> attrs;
  std::vector<std::pair<long, long>> inputs;  // (node_id, out_index)
};

struct Predictor {
  std::vector<Node> nodes;
  std::vector<std::pair<long, long>> heads;
  std::map<std::string, Tensor> params;
  std::map<std::string, long> var_nodes;          // name -> node id
  std::vector<std::vector<Tensor>> values;        // per node outputs
  std::vector<Tensor> inputs_by_node;             // bound inputs
  std::vector<Tensor> outputs;
  std::string last_error;

  void load_graph(const std::string& json) {
    JParser parser(json);
    JValue root = parser.parse();
    const JValue& jnodes = root["nodes"];
    for (const JValue& jn : jnodes.arr) {
      Node n;
      n.op = jn["op"].str;
      n.name = jn["name"].str;
      for (auto& kv : jn["attrs"].obj) n.attrs[kv.first] = kv.second.str;
      for (const JValue& ji : jn["inputs"].arr)
        n.inputs.push_back({(long)ji.arr[0].num, (long)ji.arr[1].num});
      if (n.op == "null") var_nodes[n.name] = (long)nodes.size();
      nodes.push_back(std::move(n));
    }
    for (const JValue& jh : root["heads"].arr)
      heads.push_back({(long)jh.arr[0].num, (long)jh.arr[1].num});
    values.resize(nodes.size());
  }

  void set_input(const std::string& name, const float* data,
                 const std::vector<long>& shape) {
    auto it = var_nodes.find(name);
    if (it == var_nodes.end())
      throw std::runtime_error("unknown input " + name);
    Tensor t;
    t.shape = shape;
    t.data.assign(data, data + t.size());
    values[it->second] = {std::move(t)};
  }

  const Tensor& in(const Node& n, size_t i) {
    auto [nid, oi] = n.inputs[i];
    if (values[nid].empty())
      throw std::runtime_error("node input not computed for " + n.name);
    if (oi >= (long)values[nid].size())
      throw std::runtime_error("output index " + std::to_string(oi) +
                               " out of range for node feeding " + n.name);
    return values[nid][oi];
  }

  void forward() {
    // bind parameters into variable nodes
    for (auto& [name, nid] : var_nodes) {
      if (!values[nid].empty()) continue;  // user-set input
      auto it = params.find(name);
      if (it == params.end())
        throw std::runtime_error("unbound variable " + name +
                                 " (not an input, not in params)");
      values[nid] = {it->second};
    }
    for (size_t id = 0; id < nodes.size(); ++id) {
      Node& n = nodes[id];
      if (n.op == "null") continue;
      Tensor out;
      auto a = [&](const char* k) {
        auto it = n.attrs.find(k);
        return it == n.attrs.end() ? std::string() : it->second;
      };
      if (n.op == "Convolution") {
        auto kernel = parse_tuple(a("kernel"));
        auto stride = a("stride").empty() ? std::vector<long>{1, 1}
                                          : parse_tuple(a("stride"));
        auto pad = a("pad").empty() ? std::vector<long>{0, 0}
                                    : parse_tuple(a("pad"));
        auto dilate = a("dilate").empty() ? std::vector<long>{1, 1}
                                          : parse_tuple(a("dilate"));
        bool no_bias = parse_bool(a("no_bias"), false);
        conv2d(in(n, 0), in(n, 1), no_bias ? nullptr : &in(n, 2), stride,
               pad, dilate, parse_int(a("num_group"), 1), out);
      } else if (n.op == "FullyConnected") {
        bool no_bias = parse_bool(a("no_bias"), false);
        fully_connected(in(n, 0), in(n, 1),
                        no_bias ? nullptr : &in(n, 2),
                        parse_bool(a("flatten"), true), out);
      } else if (n.op == "BatchNorm") {
        batchnorm(in(n, 0), in(n, 1), in(n, 2), in(n, 3), in(n, 4),
                  parse_float(a("eps"), 1e-3),
                  parse_bool(a("fix_gamma"), true), out);
        apply_activation(out, a("act_type"));   // nn.BatchNorm(activation=)
        values[id] = {out, in(n, 3), in(n, 4)};
        continue;
      } else if (n.op == "Activation") {
        out = in(n, 0);
        apply_activation(out, a("act_type"));
      } else if (n.op == "relu") {
        out = in(n, 0);
        apply_activation(out, "relu");
      } else if (n.op == "LeakyReLU") {
        out = in(n, 0);
        apply_activation(out, a("act_type").empty() ? "leaky" : a("act_type"),
                         (float)parse_float(a("slope"), 0.25));
      } else if (n.op == "_contrib_matmul_epilogue" ||
                 n.op == "_contrib_conv_epilogue") {
        // act(in0 + in1): the bias broadcasts over the last axis, the
        // residual is elementwise; `p` is training-only, as Dropout's
        broadcast_binary(in(n, 0), in(n, 1), 0, out);
        bool conv = n.op == "_contrib_conv_epilogue";
        apply_activation(out, conv && a("act_type").empty() ? "relu"
                                                            : a("act_type"));
      } else if (n.op == "Pooling") {
        auto kernel = a("kernel").empty() ? std::vector<long>{1, 1}
                                          : parse_tuple(a("kernel"));
        if (kernel.size() == 1) kernel.push_back(kernel[0]);
        auto stride = a("stride").empty() ? std::vector<long>{1, 1}
                                          : parse_tuple(a("stride"));
        if (stride.size() == 1) stride.push_back(stride[0]);
        auto pad = a("pad").empty() ? std::vector<long>{0, 0}
                                    : parse_tuple(a("pad"));
        if (pad.size() == 1) pad.push_back(pad[0]);
        pooling(in(n, 0), a("pool_type").empty() ? "max" : a("pool_type"),
                parse_bool(a("global_pool"), false), kernel, stride, pad,
                a("pooling_convention") == "full",
                parse_bool(a("count_include_pad"), true), out);
      } else if (n.op == "Flatten") {
        out = in(n, 0);
        long n0 = out.shape[0];
        out.shape = {n0, out.size() / n0};
      } else if (n.op == "reshape" || n.op == "Reshape") {
        out = in(n, 0);
        auto shape = parse_tuple(a("shape"));
        long known = 1, infer = -1;
        for (size_t i = 0; i < shape.size(); ++i) {
          if (shape[i] == -1) infer = (long)i;
          else if (shape[i] == 0) { shape[i] = out.shape[i]; known *= shape[i]; }
          else known *= shape[i];
        }
        if (infer >= 0) shape[infer] = out.size() / known;
        out.shape.assign(shape.begin(), shape.end());
      } else if (n.op == "elemwise_add" || n.op == "broadcast_add" ||
                 n.op == "elemwise_mul" || n.op == "broadcast_mul" ||
                 n.op == "elemwise_sub" || n.op == "broadcast_sub" ||
                 n.op == "elemwise_div" || n.op == "broadcast_div") {
        int kind = n.op.find("add") != std::string::npos ? 0
                   : n.op.find("mul") != std::string::npos ? 1
                   : n.op.find("sub") != std::string::npos ? 2 : 3;
        broadcast_binary(in(n, 0), in(n, 1), kind, out);
      } else if (n.op == "_mul_scalar" || n.op == "_plus_scalar" ||
                 n.op == "_minus_scalar" || n.op == "_rminus_scalar" ||
                 n.op == "_div_scalar" || n.op == "_rdiv_scalar") {
        out = in(n, 0);
        float s = (float)parse_float(a("scalar"), 0.0);
        for (float& v : out.data) {
          if (n.op == "_mul_scalar") v *= s;
          else if (n.op == "_plus_scalar") v += s;
          else if (n.op == "_minus_scalar") v -= s;
          else if (n.op == "_rminus_scalar") v = s - v;
          else if (n.op == "_div_scalar") v /= s;
          else v = s / v;
        }
      } else if (n.op == "Embedding") {
        embedding(in(n, 0), in(n, 1), out);
      } else if (n.op == "LayerNorm") {
        layernorm(in(n, 0), in(n, 1), in(n, 2),
                  parse_float(a("eps"), 1e-5), parse_int(a("axis"), -1),
                  out);
      } else if (n.op == "_contrib_fused_self_attention") {
        self_attention(in(n, 0), parse_int(a("heads"), 1),
                       parse_bool(a("causal"), false), out);
      } else if (n.op == "_contrib_fused_cross_attention") {
        cross_attention(in(n, 0), in(n, 1), parse_int(a("heads"), 1), out);
      } else if (n.op == "expand_dims") {
        out = in(n, 0);
        long ax = parse_int(a("axis"), 0);
        if (ax < 0) ax += (long)out.shape.size() + 1;
        out.shape.insert(out.shape.begin() + ax, 1);
      } else if (n.op == "squeeze") {
        out = in(n, 0);
        std::string axs = a("axis");
        if (axs.empty() || axs == "None") {
          std::vector<long> ns;
          for (long s : out.shape) if (s != 1) ns.push_back(s);
          if (ns.empty()) ns.push_back(1);
          out.shape = ns;
        } else {
          auto axes = parse_tuple(axs);
          std::vector<bool> drop(out.shape.size(), false);
          for (long ax : axes)
            drop[ax < 0 ? ax + (long)out.shape.size() : ax] = true;
          std::vector<long> ns;
          for (size_t i = 0; i < out.shape.size(); ++i)
            if (!drop[i]) ns.push_back(out.shape[i]);
          if (ns.empty()) ns.push_back(1);
          out.shape = ns;
        }
      } else if (n.op == "slice") {
        for (long st : parse_tuple_opt(a("step")))
          if (st != kNone && st != 1)
            throw std::runtime_error("slice: non-unit step unsupported");
        slice_ranges(in(n, 0), parse_tuple_opt(a("begin")),
                     parse_tuple_opt(a("end")), out);
      } else if (n.op == "slice_like") {
        const Tensor& x = in(n, 0);
        const Tensor& like = in(n, 1);
        std::vector<long> begin(x.shape.size(), 0);
        std::vector<long> end(x.shape.begin(), x.shape.end());
        std::string axs = a("axes");
        if (axs.empty() || axs == "None") {
          for (size_t i = 0; i < x.shape.size() && i < like.shape.size();
               ++i)
            end[i] = like.shape[i];
        } else {
          for (long ax : parse_tuple(axs)) {
            if (ax < 0) ax += (long)x.shape.size();
            end[ax] = like.shape[ax];
          }
        }
        slice_ranges(x, begin, end, out);
      } else if (n.op == "transpose") {
        out.shape.clear();
        transpose_nd(in(n, 0), a("axes").empty() ? std::vector<long>{}
                                                 : parse_tuple(a("axes")),
                     out);
      } else if (n.op == "batch_dot") {
        batch_dot(in(n, 0), in(n, 1),
                  parse_bool(a("transpose_a"), false),
                  parse_bool(a("transpose_b"), false), out);
      } else if (n.op == "Concat") {
        long dim = parse_int(a("dim"), 1);
        const Tensor& first = in(n, 0);
        out.shape = first.shape;
        long total = 0;
        for (size_t i = 0; i < n.inputs.size(); ++i) total += in(n, i).shape[dim];
        out.shape[dim] = total;
        out.alloc();
        long outer = 1, inner = 1;
        for (long d = 0; d < dim; ++d) outer *= first.shape[d];
        for (size_t d = dim + 1; d < first.shape.size(); ++d)
          inner *= first.shape[d];
        long off = 0;
        for (size_t i = 0; i < n.inputs.size(); ++i) {
          const Tensor& t = in(n, i);
          long chunk = t.shape[dim] * inner;
          for (long o = 0; o < outer; ++o)
            std::memcpy(&out.data[(o * out.shape[dim] + off) * inner],
                        &t.data[o * chunk], chunk * sizeof(float));
          off += t.shape[dim];
        }
      } else if (n.op == "softmax" || n.op == "SoftmaxOutput") {
        out = in(n, 0);
        long ax = parse_int(a("axis"), -1);
        long nd2 = (long)out.shape.size();
        if (ax != -1 && ax != nd2 - 1)
          throw std::runtime_error("softmax: only last-axis supported");
        softmax_rows(out);
      } else if (n.op == "log_softmax") {
        out = in(n, 0);
        softmax_rows(out);
        for (float& v : out.data) v = std::log(std::max(v, 1e-30f));
      } else if (n.op == "Dropout" || n.op == "identity") {
        out = in(n, 0);
      } else if (n.op == "_group") {
        // multi-output head grouping: pass every input through
        std::vector<Tensor> vals;
        for (size_t i = 0; i < n.inputs.size(); ++i) vals.push_back(in(n, i));
        values[id] = std::move(vals);
        continue;
      } else {
        throw std::runtime_error("predict: unsupported op " + n.op +
                                 " (node " + n.name + ")");
      }
      values[id] = {std::move(out)};
    }
    outputs.clear();
    for (auto [nid, oi] : heads) outputs.push_back(values[nid][oi]);
    // free intermediates, keep variables (params) for the next forward
    for (size_t id = 0; id < nodes.size(); ++id)
      if (nodes[id].op != "null") values[id].clear();
  }
};

}  // namespace predict

// ---------------------------------------------------------------------------
// C ABI (ref: include/mxnet/c_predict_api.h)
// ---------------------------------------------------------------------------
extern "C" {

typedef void* PredictorHandle;
static thread_local std::string mxpred_last_error;

const char* MXPredGetLastError() { return mxpred_last_error.c_str(); }

int MXPredCreate(const char* symbol_json, const void* param_bytes,
                 int param_size, int dev_type, int dev_id,
                 unsigned num_input_nodes, const char** input_keys,
                 const unsigned* input_shape_indptr,
                 const unsigned* input_shape_data, PredictorHandle* out) {
  (void)dev_type; (void)dev_id;
  try {
    auto p = std::make_unique<predict::Predictor>();
    p->load_graph(symbol_json);
    p->params = predict::load_params(param_bytes, (size_t)param_size);
    // the reference workflow passes input shapes here (c_predict_api.h):
    // seed them so MXPredSetInput works without a separate
    // MXPredSetInputShape call
    if (num_input_nodes > 0 && input_keys && input_shape_indptr &&
        input_shape_data) {
      p->inputs_by_node.resize(p->nodes.size());
      for (unsigned i = 0; i < num_input_nodes; ++i) {
        auto it = p->var_nodes.find(input_keys[i]);
        if (it == p->var_nodes.end())
          throw std::runtime_error(std::string("unknown input ") +
                                   input_keys[i]);
        predict::Tensor& t = p->inputs_by_node[it->second];
        t.shape.clear();
        for (unsigned d = input_shape_indptr[i];
             d < input_shape_indptr[i + 1]; ++d)
          t.shape.push_back((long)input_shape_data[d]);
      }
    }
    *out = p.release();
    return 0;
  } catch (const std::exception& e) {
    mxpred_last_error = e.what();
    return -1;
  }
}

int MXPredSetInput(PredictorHandle handle, const char* key,
                   const float* data, unsigned size) {
  auto* p = (predict::Predictor*)handle;
  try {
    auto it = p->var_nodes.find(key);
    if (it == p->var_nodes.end())
      throw std::runtime_error(std::string("unknown input ") + key);
    // shape must have been provided via MXPredSetInputShape or reuse
    if (p->inputs_by_node.empty()) p->inputs_by_node.resize(p->nodes.size());
    predict::Tensor& t = p->inputs_by_node[it->second];
    if (t.shape.empty())
      throw std::runtime_error(std::string("set shape first for ") + key);
    if ((unsigned)t.size() != size)
      throw std::runtime_error("input size mismatch");
    t.data.assign(data, data + size);
    p->values[it->second] = {t};
    return 0;
  } catch (const std::exception& e) {
    mxpred_last_error = e.what();
    return -1;
  }
}

int MXPredSetInputShape(PredictorHandle handle, const char* key,
                        const long* shape, unsigned ndim) {
  auto* p = (predict::Predictor*)handle;
  try {
    auto it = p->var_nodes.find(key);
    if (it == p->var_nodes.end())
      throw std::runtime_error(std::string("unknown input ") + key);
    if (p->inputs_by_node.empty()) p->inputs_by_node.resize(p->nodes.size());
    predict::Tensor& t = p->inputs_by_node[it->second];
    t.shape.assign(shape, shape + ndim);
    return 0;
  } catch (const std::exception& e) {
    mxpred_last_error = e.what();
    return -1;
  }
}

int MXPredForward(PredictorHandle handle) {
  auto* p = (predict::Predictor*)handle;
  try {
    p->forward();
    return 0;
  } catch (const std::exception& e) {
    mxpred_last_error = e.what();
    return -1;
  }
}

int MXPredGetOutputShape(PredictorHandle handle, unsigned index,
                         long* shape_data, unsigned* ndim) {
  auto* p = (predict::Predictor*)handle;
  try {
    if (index >= p->outputs.size())
      throw std::runtime_error("output index out of range");
    const auto& s = p->outputs[index].shape;
    *ndim = (unsigned)s.size();
    if (shape_data)
      for (size_t i = 0; i < s.size(); ++i) shape_data[i] = s[i];
    return 0;
  } catch (const std::exception& e) {
    mxpred_last_error = e.what();
    return -1;
  }
}

int MXPredGetOutput(PredictorHandle handle, unsigned index, float* data,
                    unsigned size) {
  auto* p = (predict::Predictor*)handle;
  try {
    if (index >= p->outputs.size())
      throw std::runtime_error("output index out of range");
    const predict::Tensor& t = p->outputs[index];
    if ((unsigned)t.size() != size)
      throw std::runtime_error("output size mismatch");
    std::memcpy(data, t.data.data(), size * sizeof(float));
    return 0;
  } catch (const std::exception& e) {
    mxpred_last_error = e.what();
    return -1;
  }
}

int MXPredFree(PredictorHandle handle) {
  delete (predict::Predictor*)handle;
  return 0;
}

}  // extern "C"
