// bigdl_tpu native host runtime — the C++ counterpart of the reference's
// native/near-native components (SURVEY §2.1):
//
//  * CRC32C (castagnoli, slicing-by-8) for TFRecord/tensorboard framing
//    (reference java/netty/Crc32c.java)
//  * fp16/bf16 wire codec with compressed-domain accumulate — the
//    FP16CompressedTensor plane (reference
//    parameters/FP16CompressedTensor.scala:26 toFP16/fromFP16/parAdd)
//  * multithreaded image batch assembly: normalize + NHWC->NCHW + stack
//    (reference dataset/image/MTLabeledBGRImgToBatch.scala:46)
//
// Exposed as a flat extern "C" ABI consumed via ctypes — no pybind11
// (not in the image).  All bulk loops are chunked across a std::thread
// pool, mirroring the reference's Engine.default parallel chunks.
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <functional>
#include <mutex>
#include <queue>
#include <thread>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// thread pool (reference utils/ThreadPool.scala:32 invokeAndWait analogue)
// ---------------------------------------------------------------------------
class Pool {
 public:
  explicit Pool(int n) : stop_(false) {
    for (int i = 0; i < n; ++i) {
      workers_.emplace_back([this] {
        for (;;) {
          std::function<void()> job;
          {
            std::unique_lock<std::mutex> lk(m_);
            cv_.wait(lk, [this] { return stop_ || !jobs_.empty(); });
            if (stop_ && jobs_.empty()) return;
            job = std::move(jobs_.front());
            jobs_.pop();
          }
          job();
        }
      });
    }
  }
  ~Pool() {
    {
      std::lock_guard<std::mutex> lk(m_);
      stop_ = true;
    }
    cv_.notify_all();
    for (auto& w : workers_) w.join();
  }
  // run fn(chunk_index) for chunks [0, nchunks) and wait
  void parallel_for(int64_t nchunks, const std::function<void(int64_t)>& fn) {
    if (nchunks <= 1) {
      for (int64_t i = 0; i < nchunks; ++i) fn(i);
      return;
    }
    std::atomic<int64_t> done(0);
    std::mutex dm;
    std::condition_variable dcv;
    for (int64_t i = 0; i < nchunks; ++i) {
      std::function<void()> job = [&, i] {
        fn(i);
        if (done.fetch_add(1) + 1 == nchunks) {
          std::lock_guard<std::mutex> lk(dm);
          dcv.notify_one();
        }
      };
      {
        std::lock_guard<std::mutex> lk(m_);
        jobs_.push(std::move(job));
      }
      cv_.notify_one();
    }
    std::unique_lock<std::mutex> lk(dm);
    dcv.wait(lk, [&] { return done.load() == nchunks; });
  }
  int size() const { return static_cast<int>(workers_.size()); }

 private:
  std::vector<std::thread> workers_;
  std::queue<std::function<void()>> jobs_;
  std::mutex m_;
  std::condition_variable cv_;
  bool stop_;
};

Pool& pool() {
  static Pool p(std::max(2u, std::thread::hardware_concurrency()));
  return p;
}

inline void chunked(int64_t n, int64_t min_chunk,
                    const std::function<void(int64_t, int64_t)>& body) {
  int64_t nthreads = pool().size();
  int64_t chunk = std::max(min_chunk, (n + nthreads - 1) / nthreads);
  int64_t nchunks = (n + chunk - 1) / chunk;
  pool().parallel_for(nchunks, [&](int64_t c) {
    int64_t lo = c * chunk;
    int64_t hi = std::min(n, lo + chunk);
    body(lo, hi);
  });
}

// ---------------------------------------------------------------------------
// CRC32C slicing-by-8
// ---------------------------------------------------------------------------
uint32_t kCrcTable[8][256];
bool init_crc() {
  const uint32_t poly = 0x82F63B78u;
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = i;
    for (int k = 0; k < 8; ++k) c = (c & 1) ? (c >> 1) ^ poly : c >> 1;
    kCrcTable[0][i] = c;
  }
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = kCrcTable[0][i];
    for (int t = 1; t < 8; ++t) {
      c = kCrcTable[0][c & 0xFF] ^ (c >> 8);
      kCrcTable[t][i] = c;
    }
  }
  return true;
}
const bool crc_inited = init_crc();

}  // namespace

extern "C" {

uint32_t btpu_crc32c(const uint8_t* data, int64_t n, uint32_t crc) {
  crc ^= 0xFFFFFFFFu;
  while (n >= 8) {
    uint32_t lo;
    uint32_t hi;
    std::memcpy(&lo, data, 4);
    std::memcpy(&hi, data + 4, 4);
    lo ^= crc;
    crc = kCrcTable[7][lo & 0xFF] ^ kCrcTable[6][(lo >> 8) & 0xFF] ^
          kCrcTable[5][(lo >> 16) & 0xFF] ^ kCrcTable[4][lo >> 24] ^
          kCrcTable[3][hi & 0xFF] ^ kCrcTable[2][(hi >> 8) & 0xFF] ^
          kCrcTable[1][(hi >> 16) & 0xFF] ^ kCrcTable[0][hi >> 24];
    data += 8;
    n -= 8;
  }
  while (n-- > 0) crc = kCrcTable[0][(crc ^ *data++) & 0xFF] ^ (crc >> 8);
  return crc ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------------------
// fp16/bf16 codec (FP16CompressedTensor parity: fp32 -> high-2-bytes
// truncation, i.e. bf16 bit pattern; the reference's "FP16" IS the
// truncated-fp32 format, FP16CompressedTensor.scala:173-199)
// ---------------------------------------------------------------------------
namespace {
inline uint16_t f32_bits_to_bf16(uint32_t bits) {
  if ((bits & 0x7F800000u) == 0x7F800000u && (bits & 0x007FFFFFu)) {
    // NaN: truncate but force a quiet-NaN payload so rounding can't
    // overflow it into ±inf/-0
    return static_cast<uint16_t>((bits >> 16) | 0x0040u);
  }
  // round-to-nearest-even on the truncated mantissa
  uint32_t rounding = 0x7FFFu + ((bits >> 16) & 1u);
  return static_cast<uint16_t>((bits + rounding) >> 16);
}
}  // namespace

void btpu_f32_to_bf16(const float* src, uint16_t* dst, int64_t n) {
  chunked(n, 1 << 15, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      uint32_t bits;
      std::memcpy(&bits, src + i, 4);
      dst[i] = f32_bits_to_bf16(bits);
    }
  });
}

void btpu_bf16_to_f32(const uint16_t* src, float* dst, int64_t n) {
  chunked(n, 1 << 15, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      uint32_t bits = static_cast<uint32_t>(src[i]) << 16;
      std::memcpy(dst + i, &bits, 4);
    }
  });
}

// compressed-domain accumulate: dst[i] += src[i] in bf16 wire format
// (reference FP16CompressedTensor.parAdd:122-152)
void btpu_bf16_add(uint16_t* dst, const uint16_t* src, int64_t n) {
  chunked(n, 1 << 15, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      uint32_t a = static_cast<uint32_t>(dst[i]) << 16;
      uint32_t b = static_cast<uint32_t>(src[i]) << 16;
      float fa;
      float fb;
      std::memcpy(&fa, &a, 4);
      std::memcpy(&fb, &b, 4);
      float s = fa + fb;
      uint32_t bits;
      std::memcpy(&bits, &s, 4);
      dst[i] = f32_bits_to_bf16(bits);
    }
  });
}

// ---------------------------------------------------------------------------
// multithreaded batch assembly (MTLabeledBGRImgToBatch parity):
// n HWC uint8 images -> one NCHW float batch, normalized, one thread per
// image-chunk.
// ---------------------------------------------------------------------------
void btpu_batch_images_u8(const uint8_t* images, int64_t n, int64_t h,
                          int64_t w, int64_t c, const float* mean,
                          const float* stdv, float* out) {
  const int64_t img = h * w * c;
  const int64_t plane = h * w;
  chunked(n, 1, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      const uint8_t* src = images + i * img;
      float* dst = out + i * img;
      for (int64_t y = 0; y < h; ++y)
        for (int64_t x = 0; x < w; ++x)
          for (int64_t ch = 0; ch < c; ++ch)
            dst[ch * plane + y * w + x] =
                (static_cast<float>(src[(y * w + x) * c + ch]) - mean[ch]) /
                stdv[ch];
    }
  });
}

// float HWC variant (already-decoded images)
void btpu_batch_images_f32(const float* images, int64_t n, int64_t h,
                           int64_t w, int64_t c, const float* mean,
                           const float* stdv, float* out) {
  const int64_t img = h * w * c;
  const int64_t plane = h * w;
  chunked(n, 1, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      const float* src = images + i * img;
      float* dst = out + i * img;
      for (int64_t y = 0; y < h; ++y)
        for (int64_t x = 0; x < w; ++x)
          for (int64_t ch = 0; ch < c; ++ch)
            dst[ch * plane + y * w + x] =
                (src[(y * w + x) * c + ch] - mean[ch]) / stdv[ch];
    }
  });
}

// ---------------------------------------------------------------------------
// Record-file framing scan (the ingest hot loop): walk a TFRecord-framed
// buffer (len | crc(len) | data | crc(data)), verify both masked CRC32Cs,
// and emit (offset, length) pairs for the data payloads.  Returns the
// record count, or -(byte position + 1) at the first corruption.
// ---------------------------------------------------------------------------
namespace {
inline uint32_t masked_crc(const uint8_t* data, int64_t n) {
  uint32_t crc = btpu_crc32c(data, n, 0);
  return (((crc >> 15) | (crc << 17)) + 0xA282EAD8u);
}
inline uint32_t load_u32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}
}  // namespace

int64_t btpu_parse_records(const uint8_t* buf, int64_t n, int64_t* offsets,
                           int64_t* lengths, int64_t max_records,
                           int verify) {
  int64_t pos = 0;
  int64_t count = 0;
  while (pos + 12 <= n && count < max_records) {
    uint64_t len;
    std::memcpy(&len, buf + pos, 8);
    // unsigned check first: a length with high bits set must not wrap
    // negative and slip past the bounds arithmetic below
    if (len > static_cast<uint64_t>(n) ||
        pos + 16 + static_cast<int64_t>(len) > n)
      return -(pos + 1);
    if (verify) {
      if (load_u32(buf + pos + 8) != masked_crc(buf + pos, 8))
        return -(pos + 1);
      if (load_u32(buf + pos + 12 + len) != masked_crc(buf + pos + 12, len))
        return -(pos + 1);
    }
    offsets[count] = pos + 12;
    lengths[count] = static_cast<int64_t>(len);
    ++count;
    pos += 16 + static_cast<int64_t>(len);
  }
  return count;
}

int btpu_num_threads() { return pool().size(); }

// The Makefile stamps the sha256 of this file into the library; the
// python loader greps the .so for the tag and rebuilds on a mismatch,
// so a library built from other source is never loaded.
#ifndef BTPU_SOURCE_SHA256
#define BTPU_SOURCE_SHA256 "unstamped"
#endif
const char* btpu_source_tag() {
  return "btpu-source-sha256:" BTPU_SOURCE_SHA256;
}

}  // extern "C"
