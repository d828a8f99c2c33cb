/*
 * prefetch.cc — multi-threaded prefetching batch loader.
 *
 * The native equivalent of the reference's PrefetcherIter +
 * BatchLoader + ImageRecordIOParser2 pipeline (src/io/iter_prefetcher.h,
 * iter_batchloader.h, iter_image_recordio_2.cc): worker threads claim
 * whole batches, read records from the mmap'd RecordIO file, optionally
 * JPEG-decode + resize them, and publish completed batches into a
 * bounded, order-preserving queue the Python thread consumes.
 */
#include "mxtpu.h"

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

void mxtpu_bilinear_resize_rgb(const uint8_t *src, int sh, int sw,
                               uint8_t *dst, int dh, int dw);

namespace {

struct Batch {
  std::vector<uint8_t> data;
  std::vector<uint8_t> aux;   // int64 offsets (mode 0) or float labels (mode 1)
  int64_t n_records = 0;
};

struct IRHeader {
  uint32_t flag;
  float label;
  uint64_t id;
  uint64_t id2;
};

int64_t NowNs() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// What mxtpu_prefetch_stats reports: counted over the handle's life, resets
// included, with relaxed atomics (each is a sum, nothing is ordered by them).
struct Stats {
  std::atomic<int64_t> decoded{0};   // records read (+ decoded) into batches
  std::atomic<int64_t> busy_ns{0};   // workers in BuildBatch, summed
  std::atomic<int64_t> full_ns{0};   // workers before a full queue, summed
  std::atomic<int64_t> empty_ns{0};  // the consumer before an empty queue
};

void Add(std::atomic<int64_t> &sum, int64_t value) {
  sum.fetch_add(value, std::memory_order_relaxed);
}

struct Prefetcher {
  void *reader = nullptr;
  std::vector<int64_t> indices;
  int64_t batch_size = 0;
  int32_t mode = 0;
  int32_t edge = 0;
  int32_t label_width = 1;
  int64_t n_batches = 0;

  std::vector<std::thread> workers;
  std::atomic<int64_t> next_claim{0};
  int64_t next_deliver = 0;

  std::mutex mu;
  std::condition_variable cv_produce;  // workers wait: queue has room
  std::condition_variable cv_consume;  // consumer waits: next batch ready
  std::map<int64_t, std::unique_ptr<Batch>> ready;
  size_t queue_depth = 4;
  bool stop = false;
  bool failed = false;
  std::string error;

  std::unique_ptr<Batch> current;  // batch handed to Python, kept alive
  std::mutex read_mu;              // RecordIO scratch buffer is per-handle
  int n_threads = 4;
  Stats stats;
};

void BuildBatch(Prefetcher *p, int64_t b, Batch *out) {
  int64_t start = b * p->batch_size;
  int64_t end = std::min<int64_t>(start + p->batch_size,
                                  static_cast<int64_t>(p->indices.size()));
  int64_t n = end - start;
  out->n_records = n;
  if (p->mode == 0) {
    std::vector<int64_t> offsets(static_cast<size_t>(n) + 1, 0);
    for (int64_t i = 0; i < n; ++i) {
      void *ptr = nullptr;
      int64_t sz;
      {
        std::lock_guard<std::mutex> lk(p->read_mu);
        sz = mxtpu_recordio_read(p->reader, p->indices[start + i], &ptr);
        if (sz < 0) throw std::runtime_error("record read failed");
        out->data.insert(out->data.end(), static_cast<uint8_t *>(ptr),
                         static_cast<uint8_t *>(ptr) + sz);
      }
      offsets[i + 1] = offsets[i] + sz;
    }
    out->aux.resize(offsets.size() * sizeof(int64_t));
    std::memcpy(out->aux.data(), offsets.data(), out->aux.size());
    return;
  }
  // mode 1: image batch, NHWC uint8 + float32 labels
  const int e = p->edge;
  out->data.assign(static_cast<size_t>(n) * e * e * 3, 0);
  std::vector<float> labels(static_cast<size_t>(n) * p->label_width, 0.f);
  std::vector<uint8_t> record, decoded, resized;
  for (int64_t i = 0; i < n; ++i) {
    {
      std::lock_guard<std::mutex> lk(p->read_mu);
      void *ptr = nullptr;
      int64_t sz = mxtpu_recordio_read(p->reader, p->indices[start + i], &ptr);
      if (sz < 0) throw std::runtime_error("record read failed");
      record.assign(static_cast<uint8_t *>(ptr),
                    static_cast<uint8_t *>(ptr) + sz);
    }
    if (record.size() < sizeof(IRHeader))
      throw std::runtime_error("record too small for IRHeader");
    IRHeader hdr;
    std::memcpy(&hdr, record.data(), sizeof(IRHeader));
    const uint8_t *payload = record.data() + sizeof(IRHeader);
    size_t payload_size = record.size() - sizeof(IRHeader);
    if (hdr.flag > 0) {  // label array follows the header
      size_t lab_bytes = static_cast<size_t>(hdr.flag) * 4;
      if (payload_size < lab_bytes)
        throw std::runtime_error("label array exceeds record");
      int nl = std::min<int>(p->label_width, static_cast<int>(hdr.flag));
      std::memcpy(&labels[i * p->label_width], payload, nl * 4);
      payload += lab_bytes;
      payload_size -= lab_bytes;
    } else {
      labels[i * p->label_width] = hdr.label;
    }
    int32_t h, w, c;
    if (mxtpu_jpeg_decode(payload, static_cast<int64_t>(payload_size),
                          nullptr, 0, &h, &w, &c) != 0)
      throw std::runtime_error("jpeg header parse failed");
    decoded.resize(static_cast<size_t>(h) * w * 3);
    if (mxtpu_jpeg_decode(payload, static_cast<int64_t>(payload_size),
                          decoded.data(),
                          static_cast<int64_t>(decoded.size()), &h, &w,
                          &c) != 0)
      throw std::runtime_error("jpeg decode failed");
    // Short-side resize then center crop to edge x edge.
    int rh, rw;
    if (h < w) {
      rh = e;
      rw = static_cast<int>(static_cast<int64_t>(w) * e / h);
    } else {
      rw = e;
      rh = static_cast<int>(static_cast<int64_t>(h) * e / w);
    }
    resized.resize(static_cast<size_t>(rh) * rw * 3);
    mxtpu_bilinear_resize_rgb(decoded.data(), h, w, resized.data(), rh, rw);
    int y0 = (rh - e) / 2, x0 = (rw - e) / 2;
    uint8_t *dst = out->data.data() + static_cast<size_t>(i) * e * e * 3;
    for (int y = 0; y < e; ++y)
      std::memcpy(dst + static_cast<size_t>(y) * e * 3,
                  resized.data() + (static_cast<size_t>(y0 + y) * rw + x0) * 3,
                  static_cast<size_t>(e) * 3);
  }
  out->aux.resize(labels.size() * sizeof(float));
  std::memcpy(out->aux.data(), labels.data(), out->aux.size());
}

void StopWorkers(Prefetcher *p) {
  {
    std::lock_guard<std::mutex> lk(p->mu);
    p->stop = true;
    p->cv_produce.notify_all();
  }
  for (auto &t : p->workers) t.join();
  p->workers.clear();
}

void WorkerLoop(Prefetcher *p) {
  for (;;) {
    int64_t b = p->next_claim.fetch_add(1);
    if (b >= p->n_batches) return;
    auto batch = std::make_unique<Batch>();
    int64_t t_build = NowNs();
    try {
      BuildBatch(p, b, batch.get());
    } catch (const std::exception &ex) {
      std::lock_guard<std::mutex> lk(p->mu);
      p->failed = true;
      p->error = ex.what();
      p->cv_consume.notify_all();
      return;
    }
    int64_t t_built = NowNs();
    Add(p->stats.busy_ns, t_built - t_build);
    Add(p->stats.decoded, batch->n_records);
    std::unique_lock<std::mutex> lk(p->mu);
    p->cv_produce.wait(lk, [&] {
      return p->stop || p->ready.size() < p->queue_depth ||
             b < p->next_deliver + static_cast<int64_t>(p->queue_depth);
    });
    Add(p->stats.full_ns, NowNs() - t_built);
    if (p->stop) return;
    p->ready.emplace(b, std::move(batch));
    p->cv_consume.notify_all();
  }
}

}  // namespace

extern "C" {

void *mxtpu_prefetch_create(const char *rec_path, const int64_t *indices,
                            int64_t n_indices, int64_t batch_size,
                            int32_t n_threads, int32_t queue_depth,
                            int32_t mode, int32_t edge, int32_t label_width) {
  if (batch_size <= 0 || n_indices < 0 || (mode == 1 && edge <= 0))
    return nullptr;
  void *reader = mxtpu_recordio_open(rec_path);
  if (!reader) return nullptr;
  auto *p = new Prefetcher();
  p->reader = reader;
  p->indices.assign(indices, indices + n_indices);
  p->batch_size = batch_size;
  p->mode = mode;
  p->edge = edge;
  p->label_width = label_width > 0 ? label_width : 1;
  p->n_batches = (n_indices + batch_size - 1) / batch_size;
  p->queue_depth = queue_depth > 0 ? static_cast<size_t>(queue_depth) : 4;
  p->n_threads = n_threads > 0 ? n_threads : 4;
  for (int t = 0; t < p->n_threads; ++t)
    p->workers.emplace_back(WorkerLoop, p);
  return p;
}

int64_t mxtpu_prefetch_next(void *handle, void **data, int64_t *data_size,
                            void **aux) {
  auto *p = static_cast<Prefetcher *>(handle);
  if (!p) return -1;
  if (p->next_deliver >= p->n_batches) return 0;  // end of epoch
  int64_t t_wait = NowNs();
  std::unique_lock<std::mutex> lk(p->mu);
  p->cv_consume.wait(lk, [&] {
    return p->failed || p->ready.count(p->next_deliver) > 0;
  });
  Add(p->stats.empty_ns, NowNs() - t_wait);
  if (p->failed) return -1;  // message available via mxtpu_prefetch_error
  p->current = std::move(p->ready[p->next_deliver]);
  p->ready.erase(p->next_deliver);
  ++p->next_deliver;
  p->cv_produce.notify_all();
  *data = p->current->data.data();
  *data_size = static_cast<int64_t>(p->current->data.size());
  *aux = p->current->aux.data();
  return p->current->n_records;
}

void mxtpu_prefetch_reset(void *handle, const int64_t *indices,
                          int64_t n_indices) {
  auto *p = static_cast<Prefetcher *>(handle);
  if (!p) return;
  StopWorkers(p);
  {
    std::lock_guard<std::mutex> lk(p->mu);
    if (indices != nullptr) {
      p->indices.assign(indices, indices + n_indices);
      p->n_batches = (n_indices + p->batch_size - 1) / p->batch_size;
    }
    p->ready.clear();
    p->next_claim = 0;
    p->next_deliver = 0;
    p->stop = false;
    p->failed = false;
    p->error.clear();
  }
  for (int t = 0; t < p->n_threads; ++t)
    p->workers.emplace_back(WorkerLoop, p);
}

void mxtpu_prefetch_stats(void *handle, int64_t out[4]) {
  auto *p = static_cast<Prefetcher *>(handle);
  if (!p) return;
  out[0] = p->stats.decoded.load(std::memory_order_relaxed);
  out[1] = p->stats.busy_ns.load(std::memory_order_relaxed);
  out[2] = p->stats.full_ns.load(std::memory_order_relaxed);
  out[3] = p->stats.empty_ns.load(std::memory_order_relaxed);
}

const char *mxtpu_prefetch_error(void *handle) {
  auto *p = static_cast<Prefetcher *>(handle);
  if (!p) return "";
  std::lock_guard<std::mutex> lk(p->mu);
  return p->error.c_str();
}

void mxtpu_prefetch_free(void *handle) {
  auto *p = static_cast<Prefetcher *>(handle);
  if (!p) return;
  StopWorkers(p);
  mxtpu_recordio_close(p->reader);
  delete p;
}

}  // extern "C"
