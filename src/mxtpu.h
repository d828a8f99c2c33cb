/*
 * mxtpu.h — C ABI of the native host runtime.
 *
 * TPU-native replacement for the reference's host-side IO stack
 * (src/io/iter_image_recordio_2.cc, iter_prefetcher.h, iter_batchloader.h
 * and dmlc-core/src/recordio). The XLA runtime owns the device; this
 * library owns the host work that feeds it: RecordIO scanning/reading,
 * JPEG decode, and a prefetching batch-assembly thread pool.
 *
 * All functions are exported with C linkage for ctypes consumption from
 * mxnet_tpu/utils/native.py. Error convention: pointer-returning calls
 * return NULL on failure, count/size-returning calls return a negative
 * value; mxtpu_last_error() gives a human-readable message.
 */
#ifndef MXTPU_H_
#define MXTPU_H_

#include <stdint.h>
#include <stddef.h>

#ifdef __cplusplus
extern "C" {
#endif

/* ------------------------------------------------------------------ */
/* Error handling                                                      */
const char *mxtpu_last_error(void);

/* ------------------------------------------------------------------ */
/* RecordIO reader: mmap the .rec file, scan magic+lrec framing once   */
/* at open to build an in-memory index, then O(1) random reads with    */
/* zero-copy for single-part records.                                  */
void *mxtpu_recordio_open(const char *path);
int64_t mxtpu_recordio_count(void *handle);
/* Returns payload size and sets *out to a pointer valid until the next
 * read on the same handle (multi-part records are assembled into a
 * per-handle scratch buffer; single-part records point into the mmap). */
int64_t mxtpu_recordio_read(void *handle, int64_t i, void **out);
void mxtpu_recordio_close(void *handle);

/* RecordIO writer (framing identical to dmlc-core recordio). */
void *mxtpu_recordio_writer_open(const char *path);
/* Returns byte offset of the record start, or -1. */
int64_t mxtpu_recordio_writer_write(void *handle, const void *buf,
                                    int64_t size);
/* Returns 0 on success, -1 if the final flush failed. */
int mxtpu_recordio_writer_close(void *handle);

/* ------------------------------------------------------------------ */
/* JPEG decode via libjpeg: RGB uint8 HWC output.                      */
/* Returns 0 on success; fills width/height/channels. If out is NULL   */
/* only the header is parsed (use to size the buffer: h*w*3).          */
int mxtpu_jpeg_decode(const void *jpeg, int64_t size, uint8_t *out,
                      int64_t out_capacity, int32_t *height,
                      int32_t *width, int32_t *channels);

/* ------------------------------------------------------------------ */
/* Prefetching batch loader: worker threads pull record indices from   */
/* a schedule, read (and optionally JPEG-decode + resize) them, and    */
/* push assembled batches into a bounded queue — the role of           */
/* PrefetcherIter + BatchLoader in the reference.                      */
/*                                                                     */
/* mode 0: raw bytes — batch is records concatenated, with per-record  */
/*         int64 offsets (n+1 entries).                                */
/* mode 1: image — each record is IRHeader(+label)+JPEG; batch is      */
/*         uint8 NHWC data (center-cropped/resized to edge x edge)     */
/*         plus float32 labels.                                        */
void *mxtpu_prefetch_create(const char *rec_path, const int64_t *indices,
                            int64_t n_indices, int64_t batch_size,
                            int32_t n_threads, int32_t queue_depth,
                            int32_t mode, int32_t edge, int32_t label_width);
/* Blocks until the next batch is ready. Returns number of records in
 * the batch (< batch_size only for the last partial batch; 0 at end of
 * epoch, -1 on error). The returned pointers are valid until the next
 * call to mxtpu_prefetch_next on the same handle.
 * mode 0: *data = concatenated bytes, *aux = int64 offsets[n+1].
 * mode 1: *data = uint8 NHWC batch,   *aux = float32 labels[n*label_width]. */
int64_t mxtpu_prefetch_next(void *handle, void **data, int64_t *data_size,
                            void **aux);
/* Restart the epoch without reopening/re-scanning the .rec file. Pass a
 * new schedule (e.g. reshuffled indices), or indices=NULL to replay the
 * current one. */
void mxtpu_prefetch_reset(void *handle, const int64_t *indices,
                          int64_t n_indices);
/* What the pool has done since it was created, resets included:
 * out[0] records read (and, in mode 1, decoded) into batches,
 * out[1] ns its threads spent reading + decoding, summed over threads,
 * out[2] ns they stood before a full queue, summed over threads,
 * out[3] ns mxtpu_prefetch_next stood before an empty one.
 * Relaxed sums: safe to read at any time, from any thread. */
void mxtpu_prefetch_stats(void *handle, int64_t out[4]);
/* Error message from the last failed mxtpu_prefetch_next on this handle. */
const char *mxtpu_prefetch_error(void *handle);
void mxtpu_prefetch_free(void *handle);

#ifdef __cplusplus
}
#endif

#endif /* MXTPU_H_ */
