// C ABI of the tpurabit native engine — the FFI surface.
//
// Capability parity with the reference's include/rabit/c_api.h:37-194
// (same Rabit* entry-point names and dtype/op enums so existing FFI
// consumers map 1:1), plus Trt* extensions: keyed variants carrying the
// caller-site bootstrap-cache key across the ABI and a custom-reducer
// entry so bindings can register reduction callbacks.
//
// All functions return 0 on success and -1 on error; the error message is
// available from TrtGetLastError().  RabitLoadCheckPoint returns the
// checkpoint version (>= 0) or -1 on error.  Buffers handed out by
// RabitLoadCheckPoint are owned by the engine and stay valid until the
// next checkpoint call; like the reference (src/c_api.cc:291-295) this
// makes the checkpoint entry points non-thread-safe (the engine API is
// single-threaded by contract anyway).
#ifndef TPURABIT_C_API_H_
#define TPURABIT_C_API_H_

#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef uint64_t trt_ulong;

/* dtype enum (matches reference python/rabit.py:209-218 numbering):
 * 0=int8 1=uint8 2=int32 3=uint32 4=int64 5=uint64 6=float32 7=float64 */
/* op enum: 0=MAX 1=MIN 2=SUM 3=BITOR */

const char* TrtGetLastError(void);

int RabitInit(int argc, char** argv);
int RabitFinalize(void);
int RabitGetRank(void);
int RabitGetWorldSize(void);
int RabitIsDistributed(void);
int RabitGetRingPrevRank(void);
int RabitTrackerPrint(const char* msg);
int RabitGetProcessorName(char* out, trt_ulong* out_len, trt_ulong max_len);

int RabitBroadcast(void* sendrecv, trt_ulong size, int root);
int RabitBroadcastKeyed(void* sendrecv, trt_ulong size, int root,
                        const char* cache_key);
int RabitAllgather(void* sendrecv, trt_ulong total_bytes, trt_ulong slice_begin,
                   trt_ulong slice_end, trt_ulong size_prev_slice);
int RabitAllgatherKeyed(void* sendrecv, trt_ulong total_bytes,
                        trt_ulong slice_begin, trt_ulong slice_end,
                        const char* cache_key);
int RabitAllreduce(void* buf, trt_ulong count, int dtype, int op,
                   void (*prepare_fn)(void*), void* prepare_arg);
int RabitAllreduceKeyed(void* buf, trt_ulong count, int dtype, int op,
                        void (*prepare_fn)(void*), void* prepare_arg,
                        const char* cache_key);
int TrtAllreduceCustom(void* buf, trt_ulong elem_size, trt_ulong count,
                       void (*reduce_fn)(void* dst, const void* src,
                                         trt_ulong count, void* ctx),
                       void* fn_ctx, void (*prepare_fn)(void*),
                       void* prepare_arg, const char* cache_key);

int RabitLoadCheckPoint(char** out_global, trt_ulong* out_global_len,
                        char** out_local, trt_ulong* out_local_len);
int RabitCheckPoint(const char* global_data, trt_ulong global_len,
                    const char* local_data, trt_ulong local_len);
/* The gather form of RabitCheckPoint: a model arrives as `n` pieces that,
 * read in order, are its blob — a small header and the memory of the
 * caller's arrays, say (rabit_tpu.checkpoint pickles out of band and hands
 * over exactly that; doc/guide.md "Checkpoint blobs").  The engine copies
 * the pieces into its own storage before the call returns — the only copy a
 * commit makes of the model — and keeps no pointer into them: the caller
 * may overwrite or free its memory at once.  What LoadCheckPoint and a
 * recovering peer get back is the pieces joined, as opaque as ever.
 * n_local == 0 (or no byte in any local piece) means no local model, as
 * local_len == 0 does above. */
typedef struct TrtBlobPiece {
  const void* data;
  trt_ulong len;
} TrtBlobPiece;
int TrtCheckPointPieces(const TrtBlobPiece* global_pieces, trt_ulong n_global,
                        const TrtBlobPiece* local_pieces, trt_ulong n_local);
int RabitLazyCheckPoint(const char* global_data, trt_ulong global_len);
/* True lazy checkpoint: `serialize_fn` is invoked only if a failure needs
 * the blob (reference global_lazycheck, allreduce_robust.cc:527-535).  It
 * must return 0 and set (*out_data, *out_len) to bytes valid until it is
 * next called; the engine copies before returning.  The callback (and the
 * model it serializes) must stay valid until the next checkpoint call. */
int TrtLazyCheckPointFn(int (*serialize_fn)(void* ctx, const char** out_data,
                                            trt_ulong* out_len),
                        void* ctx);
int RabitVersionNumber(void);
int RabitInitAfterException(void);

#ifdef __cplusplus
}  /* extern "C" */
#endif

#endif  /* TPURABIT_C_API_H_ */
