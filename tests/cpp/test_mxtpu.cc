// Native-runtime unit tests (mirrors the reference tests/cpp/ gtest layer,
// SURVEY §4: recordio roundtrip, prefetch ordering, error propagation).
// Plain asserts, no gtest dependency; exit 0 == pass.
#include <cassert>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include "../../src/mxtpu.h"

static std::string tmp_rec() {
  const char *dir = getenv("TMPDIR");
  std::string base = dir ? dir : "/tmp";
  return base + "/mxtpu_cpptest.rec";
}

static void test_recordio_roundtrip() {
  std::string path = tmp_rec();
  void *w = mxtpu_recordio_writer_open(path.c_str());
  assert(w && "writer open");
  std::vector<std::string> payloads = {"alpha", "bb", std::string(1000, 'x')};
  for (const auto &p : payloads) {
    int64_t rc = mxtpu_recordio_writer_write(w, p.data(), (int64_t)p.size());
    assert(rc >= 0 && "write");
  }
  assert(mxtpu_recordio_writer_close(w) == 0);

  void *r = mxtpu_recordio_open(path.c_str());
  assert(r && "reader open");
  assert(mxtpu_recordio_count(r) == (int64_t)payloads.size());
  for (size_t i = 0; i < payloads.size(); ++i) {
    void *buf = nullptr;
    int64_t n = mxtpu_recordio_read(r, (int64_t)i, &buf);
    assert(n == (int64_t)payloads[i].size());
    assert(memcmp(buf, payloads[i].data(), (size_t)n) == 0);
  }
  // out-of-range read fails with an error message, no crash
  void *buf = nullptr;
  int64_t n = mxtpu_recordio_read(r, 99, &buf);
  assert(n < 0);
  assert(mxtpu_last_error() && strlen(mxtpu_last_error()) > 0);
  mxtpu_recordio_close(r);
  printf("recordio roundtrip ok\n");
}

static void test_reader_missing_file() {
  void *r = mxtpu_recordio_open("/nonexistent/definitely_missing.rec");
  assert(r == nullptr);
  assert(mxtpu_last_error() && strlen(mxtpu_last_error()) > 0);
  printf("missing-file error path ok\n");
}

static void test_jpeg_decode_rejects_garbage() {
  uint8_t junk[64];
  memset(junk, 0xAB, sizeof(junk));
  uint8_t out[16 * 16 * 3];
  int32_t w = 0, h = 0, c = 0;
  int rc = mxtpu_jpeg_decode(junk, sizeof(junk), out, sizeof(out),
                             &h, &w, &c);
  assert(rc != 0 && "garbage must not decode");
  printf("jpeg garbage rejection ok\n");
}

// The pool counts what its threads did: every record built into a batch
// once, its sums never falling, a reset adding to them.
static void test_prefetch_stats() {
  std::string path = tmp_rec();  // test_recordio_roundtrip left 3 records
  int64_t order[3] = {2, 0, 1};
  void *p = mxtpu_prefetch_create(path.c_str(), order, 3, 2, 2, 2, 0, 0, 1);
  assert(p && "prefetcher create");
  int64_t before[4] = {-1, -1, -1, -1}, after[4];
  mxtpu_prefetch_stats(p, before);
  for (int k = 0; k < 4; ++k) assert(before[k] >= 0);
  for (int epoch = 1; epoch <= 2; ++epoch) {
    void *data = nullptr, *aux = nullptr;
    int64_t size = 0, n, delivered = 0;
    while ((n = mxtpu_prefetch_next(p, &data, &size, &aux)) > 0)
      delivered += n;
    assert(n == 0 && delivered == 3);
    mxtpu_prefetch_stats(p, after);
    assert(after[0] == 3 * epoch && "decoded == records delivered");
    assert(after[1] > 0 && "busy_ns");
    for (int k = 0; k < 4; ++k) {
      assert(after[k] >= before[k] && "monotone");
      before[k] = after[k];
    }
    mxtpu_prefetch_reset(p, nullptr, 0);
  }
  mxtpu_prefetch_free(p);
  printf("prefetch stats ok\n");
}

int main() {
  test_recordio_roundtrip();
  test_prefetch_stats();
  test_reader_missing_file();
  test_jpeg_decode_rejects_garbage();
  printf("ALL CPP TESTS PASSED\n");
  return 0;
}
