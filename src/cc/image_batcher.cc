// Native threaded image-decode batcher — the full TPU-native equivalent
// of the reference's src/io/iter_image_recordio_2.cc: RecordIO framing,
// IRHeader parsing, libjpeg decode, bilinear resize and batch assembly
// all run on C++ threads (no GIL), handing Python one contiguous
// uint8 CHW batch + float labels per call.
//
// Record payload layout (python recordio.pack_img): IRHeader
// "<IfQQ" = {flag:u32, label:f32, id:u64, id2:u64}; flag>0 means `flag`
// float32 multi-labels follow the header; the JPEG stream follows.
//
// C ABI (ctypes-consumed by mxnet_tpu/io/native.py):
//   mximg_batcher_create / _next / _num_batches / _reset / _close
//   mximg_decode (single-image decode+resize, for tests)

#include <cstddef>
#include <cstdio>

#include <jpeglib.h>

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <csetjmp>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <fstream>
#include <mutex>
#include <random>
#include <string>
#include <thread>
#include <vector>

namespace {

constexpr uint32_t kMagic = 0xced7230a;
constexpr uint32_t kLenBits = 29;
constexpr uint32_t kLenMask = (1u << kLenBits) - 1;
constexpr size_t kHeaderSize = 4 + 4 + 8 + 8;

bool ReadRecordAt(std::FILE* f, long offset, std::vector<char>* out) {
  std::fseek(f, offset, SEEK_SET);
  out->clear();
  uint32_t hdr[2];
  for (;;) {
    if (std::fread(hdr, sizeof(uint32_t), 2, f) != 2) return false;
    if (hdr[0] != kMagic) return false;
    uint32_t cflag = hdr[1] >> kLenBits;
    uint32_t len = hdr[1] & kLenMask;
    size_t pos = out->size();
    out->resize(pos + len);
    if (len && std::fread(out->data() + pos, 1, len, f) != len) return false;
    uint32_t pad = (4 - (len % 4)) % 4;
    if (pad) std::fseek(f, pad, SEEK_CUR);
    if (cflag == 0 || cflag == 3) return true;
  }
}

struct JpegErr {
  jpeg_error_mgr pub;
  jmp_buf jb;
};

void JpegErrExit(j_common_ptr cinfo) {
  auto* e = reinterpret_cast<JpegErr*>(cinfo->err);
  longjmp(e->jb, 1);
}

// Decode JPEG to RGB HWC uint8; returns false on corrupt input.
bool DecodeJpeg(const uint8_t* buf, size_t len, std::vector<uint8_t>* out,
                int* w, int* h) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = JpegErrExit;
  if (setjmp(jerr.jb)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(buf), len);
  jpeg_read_header(&cinfo, TRUE);
  cinfo.out_color_space = JCS_RGB;
  jpeg_start_decompress(&cinfo);
  *w = cinfo.output_width;
  *h = cinfo.output_height;
  out->resize(static_cast<size_t>(*w) * *h * 3);
  while (cinfo.output_scanline < cinfo.output_height) {
    JSAMPROW row = out->data() + static_cast<size_t>(cinfo.output_scanline) * *w * 3;
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  return true;
}

// Bilinear resize HWC uint8 (src w0xh0) to (w1xh1).
void ResizeBilinear(const uint8_t* src, int w0, int h0, uint8_t* dst, int w1,
                    int h1) {
  if (w0 == w1 && h0 == h1) {
    std::memcpy(dst, src, static_cast<size_t>(w1) * h1 * 3);
    return;
  }
  const float sx = static_cast<float>(w0) / w1;
  const float sy = static_cast<float>(h0) / h1;
  for (int y = 0; y < h1; ++y) {
    float fy = (y + 0.5f) * sy - 0.5f;
    int y0 = std::max(0, static_cast<int>(fy));
    int y1 = std::min(h0 - 1, y0 + 1);
    float wy = fy - y0;
    if (wy < 0) wy = 0;
    for (int x = 0; x < w1; ++x) {
      float fx = (x + 0.5f) * sx - 0.5f;
      int x0 = std::max(0, static_cast<int>(fx));
      int x1 = std::min(w0 - 1, x0 + 1);
      float wx = fx - x0;
      if (wx < 0) wx = 0;
      for (int c = 0; c < 3; ++c) {
        float v00 = src[(static_cast<size_t>(y0) * w0 + x0) * 3 + c];
        float v01 = src[(static_cast<size_t>(y0) * w0 + x1) * 3 + c];
        float v10 = src[(static_cast<size_t>(y1) * w0 + x0) * 3 + c];
        float v11 = src[(static_cast<size_t>(y1) * w0 + x1) * 3 + c];
        float v = v00 * (1 - wy) * (1 - wx) + v01 * (1 - wy) * wx +
                  v10 * wy * (1 - wx) + v11 * wy * wx;
        dst[(static_cast<size_t>(y) * w1 + x) * 3 + c] =
            static_cast<uint8_t>(v + 0.5f);
      }
    }
  }
}

struct ImgBatch {
  std::vector<uint8_t> data;   // B*3*H*W (CHW per image)
  std::vector<float> labels;   // B
  int64_t n = 0;
};

struct ImgBatcher {
  std::string path;
  std::vector<int64_t> index;
  std::vector<int64_t> order;
  size_t batch_size = 1;
  int out_h = 224, out_w = 224;
  bool shuffle = false;
  uint64_t seed = 0;
  size_t epoch = 0;
  size_t prefetch = 6;
  int num_threads = 4;

  std::mutex mu;
  std::condition_variable cv_produce, cv_consume;
  std::deque<ImgBatch*> ready;
  std::deque<std::pair<size_t, ImgBatch*>> out_of_order;
  std::vector<std::thread> workers;
  std::atomic<bool> stop{false};
  size_t next_batch_id = 0;
  size_t emit_batch_id = 0;

  ~ImgBatcher() { Shutdown(); }

  size_t NumBatches() const { return order.size() / batch_size; }  // discard

  void Shutdown() {
    stop.store(true);
    cv_produce.notify_all();
    cv_consume.notify_all();
    for (auto& t : workers)
      if (t.joinable()) t.join();
    workers.clear();
    for (auto* b : ready) delete b;
    ready.clear();
    for (auto& p : out_of_order) delete p.second;
    out_of_order.clear();
  }

  void StartEpoch() {
    Shutdown();
    stop.store(false);
    if (shuffle) {
      std::mt19937_64 rng(seed + epoch);
      std::shuffle(order.begin(), order.end(), rng);
    }
    next_batch_id = 0;
    emit_batch_id = 0;
    for (int i = 0; i < num_threads; ++i)
      workers.emplace_back([this] { WorkerLoop(); });
  }

  size_t NextReadyId() { return emit_batch_id + ready.size(); }

  void WorkerLoop() {
    std::FILE* f = std::fopen(path.c_str(), "rb");
    if (!f) {
      // wake any blocked consumer instead of leaving it waiting forever
      stop.store(true);
      cv_consume.notify_all();
      cv_produce.notify_all();
      return;
    }
    std::vector<char> rec;
    std::vector<uint8_t> decoded;
    const size_t img_bytes = static_cast<size_t>(out_h) * out_w * 3;
    std::vector<uint8_t> resized(img_bytes);
    while (!stop.load()) {
      size_t my_batch;
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_produce.wait(lk, [this] {
          return stop.load() || (next_batch_id < NumBatches() &&
                                 ready.size() + out_of_order.size() < prefetch);
        });
        if (stop.load() || next_batch_id >= NumBatches()) break;
        my_batch = next_batch_id++;
      }
      auto* b = new ImgBatch();
      b->data.resize(batch_size * img_bytes);
      b->labels.resize(batch_size, 0.0f);
      size_t begin = my_batch * batch_size;
      size_t filled = 0;  // corrupt records are SKIPPED, not zero-filled
      for (size_t i = 0; i < batch_size; ++i) {
        if (!ReadRecordAt(f, static_cast<long>(index[order[begin + i]]), &rec))
          continue;
        if (rec.size() < kHeaderSize) continue;
        uint32_t flag;
        float label;
        std::memcpy(&flag, rec.data(), 4);
        std::memcpy(&label, rec.data() + 4, 4);
        size_t img_off = kHeaderSize + (flag > 0 ? flag * 4ul : 0);
        if (flag > 0)  // multi-label: use the first
          std::memcpy(&label, rec.data() + kHeaderSize, 4);
        if (img_off >= rec.size()) continue;
        int w = 0, h = 0;
        if (!DecodeJpeg(reinterpret_cast<const uint8_t*>(rec.data()) + img_off,
                        rec.size() - img_off, &decoded, &w, &h))
          continue;
        ResizeBilinear(decoded.data(), w, h, resized.data(), out_w, out_h);
        // HWC -> CHW into the next filled slot (compacted batch)
        uint8_t* slot = b->data.data() + filled * img_bytes;
        const size_t plane = static_cast<size_t>(out_h) * out_w;
        for (size_t p = 0; p < plane; ++p) {
          slot[p] = resized[p * 3];
          slot[plane + p] = resized[p * 3 + 1];
          slot[2 * plane + p] = resized[p * 3 + 2];
        }
        b->labels[filled] = label;
        ++filled;
      }
      b->n = static_cast<int64_t>(filled);
      {
        std::unique_lock<std::mutex> lk(mu);
        out_of_order.emplace_back(my_batch, b);
        bool moved = true;
        while (moved) {
          moved = false;
          for (auto it = out_of_order.begin(); it != out_of_order.end(); ++it) {
            if (it->first == NextReadyId()) {
              ready.push_back(it->second);
              out_of_order.erase(it);
              moved = true;
              break;
            }
          }
        }
        cv_consume.notify_all();
      }
    }
    std::fclose(f);
  }

  ImgBatch* Next() {
    std::unique_lock<std::mutex> lk(mu);
    cv_consume.wait(lk, [this] {
      return stop.load() || !ready.empty() || (emit_batch_id >= NumBatches());
    });
    if (ready.empty()) return nullptr;
    ImgBatch* b = ready.front();
    ready.pop_front();
    ++emit_batch_id;
    cv_produce.notify_all();
    return b;
  }
};

std::vector<int64_t> LoadIdx(const std::string& idx_path) {
  std::vector<int64_t> out;
  std::ifstream in(idx_path);
  std::string line;
  while (std::getline(in, line)) {
    auto tab = line.find('\t');
    if (tab == std::string::npos) continue;
    out.push_back(std::stoll(line.substr(tab + 1)));
  }
  return out;
}

}  // namespace

extern "C" {

void* mximg_batcher_create(const char* rec_path, const char* idx_path,
                           int64_t batch_size, int out_h, int out_w,
                           int num_threads, int shuffle, uint64_t seed,
                           int64_t num_parts, int64_t part_index) {
  auto* b = new ImgBatcher();
  b->path = rec_path;
  b->batch_size = static_cast<size_t>(batch_size);
  b->out_h = out_h;
  b->out_w = out_w;
  b->num_threads = num_threads > 0 ? num_threads : 4;
  b->shuffle = shuffle != 0;
  b->seed = seed;
  // validate the .rec opens NOW: a stale idx pointing at a moved file
  // must fail at create(), not hang the first next()
  std::FILE* probe = std::fopen(rec_path, "rb");
  if (!probe) {
    delete b;
    return nullptr;
  }
  std::fclose(probe);
  b->index = LoadIdx(idx_path);
  if (b->index.empty()) {
    delete b;
    return nullptr;
  }
  for (size_t i = part_index < 0 ? 0 : static_cast<size_t>(part_index);
       i < b->index.size();
       i += (num_parts > 1 ? static_cast<size_t>(num_parts) : 1)) {
    b->order.push_back(static_cast<int64_t>(i));
  }
  if (b->order.size() < b->batch_size) {
    delete b;
    return nullptr;
  }
  b->StartEpoch();
  return b;
}

int64_t mximg_batcher_num_batches(void* handle) {
  return static_cast<int64_t>(static_cast<ImgBatcher*>(handle)->NumBatches());
}

// Copies the next batch into caller buffers (data: B*3*H*W uint8,
// labels: B float32). Returns records filled (may be < batch_size when
// corrupt records were skipped — the batch is compacted), or -1 at
// epoch end.
int64_t mximg_batcher_next(void* handle, uint8_t* data, float* labels) {
  auto* b = static_cast<ImgBatcher*>(handle);
  ImgBatch* batch = b->Next();
  if (!batch) return -1;
  std::memcpy(data, batch->data.data(), batch->data.size());
  std::memcpy(labels, batch->labels.data(),
              batch->labels.size() * sizeof(float));
  int64_t n = batch->n;
  delete batch;
  return n;
}

void mximg_batcher_reset(void* handle) {
  auto* b = static_cast<ImgBatcher*>(handle);
  ++b->epoch;
  b->StartEpoch();
}

void mximg_batcher_close(void* handle) {
  delete static_cast<ImgBatcher*>(handle);
}

// Single-image decode+resize for tests: returns 0 on success.
int mximg_decode(const uint8_t* buf, int64_t len, int out_h, int out_w,
                 uint8_t* out_chw) {
  std::vector<uint8_t> decoded;
  int w = 0, h = 0;
  if (!DecodeJpeg(buf, static_cast<size_t>(len), &decoded, &w, &h)) return -1;
  std::vector<uint8_t> resized(static_cast<size_t>(out_h) * out_w * 3);
  ResizeBilinear(decoded.data(), w, h, resized.data(), out_w, out_h);
  const size_t plane = static_cast<size_t>(out_h) * out_w;
  for (size_t p = 0; p < plane; ++p) {
    out_chw[p] = resized[p * 3];
    out_chw[plane + p] = resized[p * 3 + 1];
    out_chw[2 * plane + p] = resized[p * 3 + 2];
  }
  return 0;
}

}  // extern "C"
