// C ABI — the FFI surface (capability parity with the reference's
// include/rabit/c_api.h + src/c_api.cc 15 entry points, same dtype/op
// enums so bindings are interchangeable).  All functions return 0 on
// success, -1 on error with the message available from TrtGetLastError();
// buffers handed out by LoadCheckPoint are owned by the engine and valid
// until the next checkpoint call (like the reference's static buffers,
// c_api.cc:291-295, and equally not thread-safe).
#include <cstring>
#include <functional>
#include <string>

#include "engine.h"

using namespace tpurabit;

namespace {
thread_local std::string g_last_error;
std::string g_ckpt_global, g_ckpt_local;  // LoadCheckPoint out-buffers

int Guard(const std::function<void()>& fn) {
  try {
    fn();
    return 0;
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
}
}  // namespace

extern "C" {

typedef uint64_t trt_ulong;

const char* TrtGetLastError() { return g_last_error.c_str(); }

int RabitInit(int argc, char** argv) {
  return Guard([&] { InitEngine(argc, argv); });
}

int RabitFinalize() {
  return Guard([] { FinalizeEngine(); });
}

int RabitGetRank() { return GetEngine()->rank(); }

int RabitGetWorldSize() { return GetEngine()->world(); }

int RabitIsDistributed() { return GetEngine()->distributed() ? 1 : 0; }

int RabitGetRingPrevRank() { return GetEngine()->ring_prev(); }

int RabitTrackerPrint(const char* msg) {
  return Guard([&] { GetEngine()->TrackerPrint(msg != nullptr ? msg : ""); });
}

int RabitGetProcessorName(char* out, trt_ulong* out_len, trt_ulong max_len) {
  return Guard([&] {
    std::string h = GetEngine()->host();
    size_t n = h.size() < max_len ? h.size() : max_len - 1;
    memcpy(out, h.data(), n);
    out[n] = '\0';
    *out_len = n;
  });
}

int RabitBroadcast(void* sendrecv, trt_ulong size, int root) {
  return Guard([&] { GetEngine()->Broadcast(sendrecv, size, root, ""); });
}

int RabitBroadcastKeyed(void* sendrecv, trt_ulong size, int root,
                        const char* cache_key) {
  return Guard([&] {
    GetEngine()->Broadcast(sendrecv, size, root,
                           cache_key != nullptr ? cache_key : "");
  });
}

int RabitAllgather(void* sendrecv, trt_ulong total_bytes, trt_ulong slice_begin,
                   trt_ulong slice_end, trt_ulong /*size_prev_slice*/) {
  return Guard([&] {
    GetEngine()->Allgather(sendrecv, total_bytes, slice_begin, slice_end, "");
  });
}

int RabitAllgatherKeyed(void* sendrecv, trt_ulong total_bytes,
                        trt_ulong slice_begin, trt_ulong slice_end,
                        const char* cache_key) {
  return Guard([&] {
    GetEngine()->Allgather(sendrecv, total_bytes, slice_begin, slice_end,
                           cache_key != nullptr ? cache_key : "");
  });
}

int RabitAllreduce(void* buf, trt_ulong count, int dtype, int op,
                   void (*prepare_fn)(void*), void* prepare_arg) {
  return Guard([&] {
    ReduceFn fn = BuiltinReducer(op, dtype);
    TRT_CHECK(fn != nullptr, "unsupported op %d for dtype %d", op, dtype);
    GetEngine()->Allreduce(buf, DTypeSize(dtype), count, fn, nullptr,
                           prepare_fn, prepare_arg, "");
  });
}

int RabitAllreduceKeyed(void* buf, trt_ulong count, int dtype, int op,
                        void (*prepare_fn)(void*), void* prepare_arg,
                        const char* cache_key) {
  return Guard([&] {
    ReduceFn fn = BuiltinReducer(op, dtype);
    TRT_CHECK(fn != nullptr, "unsupported op %d for dtype %d", op, dtype);
    GetEngine()->Allreduce(buf, DTypeSize(dtype), count, fn, nullptr,
                           prepare_fn, prepare_arg,
                           cache_key != nullptr ? cache_key : "");
  });
}

// Custom reducers (the reference exposes these only at the C++ template
// layer, rabit.h:352-456; here they cross the ABI so Python can register
// one via ctypes).
int TrtAllreduceCustom(void* buf, trt_ulong elem_size, trt_ulong count,
                       void (*reduce_fn)(void*, const void*, trt_ulong, void*),
                       void* fn_ctx, void (*prepare_fn)(void*),
                       void* prepare_arg, const char* cache_key) {
  return Guard([&] {
    struct Box {
      void (*fn)(void*, const void*, trt_ulong, void*);
      void* ctx;
    } box{reduce_fn, fn_ctx};
    auto thunk = [](void* dst, const void* src, size_t n, void* c) {
      Box* b = static_cast<Box*>(c);
      b->fn(dst, src, n, b->ctx);
    };
    GetEngine()->Allreduce(buf, elem_size, count, thunk, &box, prepare_fn,
                           prepare_arg, cache_key != nullptr ? cache_key : "");
  });
}

int RabitLoadCheckPoint(char** out_global, trt_ulong* out_global_len,
                        char** out_local, trt_ulong* out_local_len) {
  int version = -1;
  int rc = Guard([&] {
    std::string g, l;
    version = GetEngine()->LoadCheckPoint(&g, &l);
    g_ckpt_global = std::move(g);
    g_ckpt_local = std::move(l);
    if (out_global != nullptr) {
      *out_global = g_ckpt_global.data();
      *out_global_len = g_ckpt_global.size();
    }
    if (out_local != nullptr) {
      *out_local = g_ckpt_local.data();
      *out_local_len = g_ckpt_local.size();
    }
  });
  return rc == 0 ? version : -1;
}

int RabitCheckPoint(const char* global_data, trt_ulong global_len,
                    const char* local_data, trt_ulong local_len) {
  return Guard([&] {
    GetEngine()->CheckPoint(global_data, global_len,
                            local_len > 0 ? local_data : nullptr, local_len);
  });
}

int TrtCheckPointPieces(const TrtBlobPiece* global_pieces, trt_ulong n_global,
                        const TrtBlobPiece* local_pieces, trt_ulong n_local) {
  return Guard([&] {
    GetEngine()->CheckPoint(BlobView{global_pieces, n_global},
                            BlobView{local_pieces, n_local});
  });
}

int RabitLazyCheckPoint(const char* global_data, trt_ulong global_len) {
  return Guard([&] { GetEngine()->LazyCheckPoint(global_data, global_len); });
}

int TrtLazyCheckPointFn(int (*serialize_fn)(void*, const char**, trt_ulong*),
                        void* ctx) {
  return Guard([&] { GetEngine()->LazyCheckPointFn(serialize_fn, ctx); });
}

int RabitVersionNumber() { return GetEngine()->VersionNumber(); }

int RabitInitAfterException() {
  return Guard([] { GetEngine()->InitAfterException(); });
}

}  // extern "C"
