// Engine interface + base (non-fault-tolerant) engine.
//
// Capability parity with the reference's IEngine seam
// (/root/reference/include/rabit/internal/engine.h:32-209) and engine
// singleton (src/engine.cc), with run-time backend selection
// (rabit_engine=empty|base|robust|mock) instead of link-time macros.
#pragma once

#include <cstdint>
#include <functional>
#include <memory>
#include <string>

#include "../include/tpurabit/c_api.h"
#include "comm.h"
#include "common.h"

namespace tpurabit {

// ABI enums shared with the Python binding (and matching the reference's
// c_api dtype/op numbering, python/rabit.py:83-86 + :209-218).
enum DataType : int {
  kInt8 = 0, kUInt8 = 1, kInt32 = 2, kUInt32 = 3,
  kInt64 = 4, kUInt64 = 5, kFloat32 = 6, kFloat64 = 7,
};
enum OpType : int { kMax = 0, kMin = 1, kSum = 2, kBitOr = 3 };

size_t DTypeSize(int dtype);
ReduceFn BuiltinReducer(int op, int dtype);  // nullptr if unsupported

using PrepareFn = void (*)(void* arg);

// Serialize-on-demand callback for true lazy checkpoints: returns 0 and a
// (data, len) view that must stay valid until the call that invoked it
// returns (the engine copies immediately).  Non-zero = serialization failed.
using SerializeFn = int (*)(void* ctx, const char** out_data,
                            uint64_t* out_len);

// One piece of a checkpoint blob, in the caller's memory: {data, len}, as
// it crosses the C ABI.
using BlobPiece = TrtBlobPiece;

// A checkpoint blob as its caller holds it: `n` pieces that, read in
// order, are the blob.  An engine copies them into storage of its own
// before CheckPoint returns and keeps no pointer into them — that copy is
// the only one a commit makes of a model handed over in pieces (PERF.md,
// PR 30).  No pieces, or none with a byte in it, is "no model".
struct BlobView {
  const BlobPiece* pieces = nullptr;
  size_t n = 0;

  size_t size() const {
    size_t total = 0;
    for (size_t i = 0; i < n; ++i) total += pieces[i].len;
    return total;
  }
  bool present() const { return size() > 0; }
  // `out` keeps its capacity from commit to commit, so a model that does
  // not grow is copied into pages that are already mapped.
  void CopyTo(std::string* out) const {
    out->clear();
    out->reserve(size());
    for (size_t i = 0; i < n; ++i) {
      out->append(static_cast<const char*>(pieces[i].data), pieces[i].len);
    }
  }
};

class Engine {
 public:
  virtual ~Engine() = default;
  virtual void Init(const Config& cfg) = 0;
  virtual void Shutdown() = 0;

  virtual int rank() const = 0;
  virtual int world() const = 0;
  virtual bool distributed() const = 0;
  virtual int ring_prev() const = 0;
  virtual std::string host() const = 0;
  virtual void TrackerPrint(const std::string& msg) = 0;

  // prepare_fn (may be null) runs right before the reduction unless the
  // result is served from recovery replay (lazy-prepare contract,
  // reference rabit.h:182-206).  cache_key is the caller-site key for the
  // bootstrap cache (reference rabit.h:29-37).
  virtual void Allreduce(void* buf, size_t elem_size, size_t count,
                         ReduceFn fn, void* fn_ctx, PrepareFn prepare_fn,
                         void* prepare_arg, const char* cache_key) = 0;
  virtual void Broadcast(void* buf, size_t size, int root,
                         const char* cache_key) = 0;
  // Rank-ordered concatenation of per-rank slices; my slice is
  // [slice_begin, slice_end) of `buf` (total_bytes long).
  virtual void Allgather(void* buf, size_t total_bytes, size_t slice_begin,
                         size_t slice_end, const char* cache_key) = 0;

  virtual int LoadCheckPoint(std::string* global_blob,
                             std::string* local_blob) = 0;
  virtual void CheckPoint(const BlobView& global, const BlobView& local) = 0;
  // The same for two contiguous blobs (the C ABI's RabitCheckPoint); a
  // null or empty local blob is no local model.
  void CheckPoint(const char* gdata, size_t glen, const char* ldata,
                  size_t llen) {
    BlobPiece g{gdata, glen}, l{ldata, llen};
    CheckPoint(BlobView{&g, 1},
               ldata != nullptr && llen > 0 ? BlobView{&l, 1} : BlobView{});
  }
  // Stores only the pointer; caller keeps the buffer alive and unchanged
  // until the next checkpoint (reference LazyCheckPoint contract,
  // rabit.h:311-332).
  virtual void LazyCheckPoint(const char* gdata, size_t glen) = 0;
  // True lazy checkpoint: serialization itself is deferred until a failure
  // actually needs the blob (reference global_lazycheck,
  // allreduce_robust.cc:527-535).  The callback must produce the same bytes
  // until the next checkpoint; non-robust engines may invoke it eagerly.
  virtual void LazyCheckPointFn(SerializeFn fn, void* ctx) {
    const char* data = nullptr;
    uint64_t len = 0;
    TRT_CHECK(fn(ctx, &data, &len) == 0, "lazy checkpoint serializer failed");
    LazyCheckPoint(data, len);
  }
  virtual int VersionNumber() const = 0;
  virtual void InitAfterException() = 0;
};

// Solo no-op engine (reference: src/engine_empty.cc) with in-memory
// versioned checkpoints so the full API works single-process.
class EmptyEngine : public Engine {
 public:
  void Init(const Config&) override {}
  void Shutdown() override {}
  int rank() const override { return 0; }
  int world() const override { return 1; }
  bool distributed() const override { return false; }
  int ring_prev() const override { return 0; }
  std::string host() const override {
    char b[256];
    gethostname(b, sizeof(b));
    return b;
  }
  void TrackerPrint(const std::string& msg) override {
    fprintf(stdout, "%s\n", msg.c_str());
    fflush(stdout);
  }
  void Allreduce(void*, size_t, size_t, ReduceFn, void*, PrepareFn prepare_fn,
                 void* prepare_arg, const char*) override {
    if (prepare_fn != nullptr) prepare_fn(prepare_arg);
  }
  void Broadcast(void*, size_t, int root, const char*) override {
    TRT_CHECK(root == 0, "broadcast root %d out of range for world 1", root);
  }
  void Allgather(void*, size_t, size_t, size_t, const char*) override {}
  int LoadCheckPoint(std::string* g, std::string* l) override {
    if (version_ > 0) {
      *g = global_;
      *l = local_;
    }
    return version_;
  }
  using Engine::CheckPoint;
  void CheckPoint(const BlobView& g, const BlobView& l) override {
    g.CopyTo(&global_);
    l.CopyTo(&local_);
    ++version_;
  }
  void LazyCheckPoint(const char* gd, size_t gl) override {
    CheckPoint(gd, gl, nullptr, 0);
  }
  int VersionNumber() const override { return version_; }
  void InitAfterException() override {
    throw Error("empty engine cannot recover from exceptions");
  }

 private:
  int version_ = 0;
  std::string global_, local_;
};

// Tree/ring collectives over TCP, no fault tolerance: a peer failure is a
// hard error (reference: AllreduceBase).
class BaseEngine : public Engine {
 public:
  void Init(const Config& cfg) override {
    // No fault tolerance here: a stall false-positive would be fatal, so
    // the liveness bound is off unless explicitly configured (the robust
    // engine keeps the on-by-default bound and recovers from one).
    comm_.SetDefaultStallSec(0);
    comm_.Configure(cfg);
    comm_.Init(/*recover=*/false);
  }
  void Shutdown() override { comm_.Shutdown(); }
  int rank() const override { return comm_.rank(); }
  int world() const override { return comm_.world(); }
  bool distributed() const override { return comm_.distributed(); }
  int ring_prev() const override { return comm_.ring_prev(); }
  std::string host() const override { return comm_.host(); }
  void TrackerPrint(const std::string& msg) override { comm_.TrackerPrint(msg); }

  void Allreduce(void* buf, size_t elem_size, size_t count, ReduceFn fn,
                 void* fn_ctx, PrepareFn prepare_fn, void* prepare_arg,
                 const char*) override {
    if (prepare_fn != nullptr) prepare_fn(prepare_arg);
    Must(comm_.Allreduce(buf, elem_size, count, fn, fn_ctx), "allreduce");
  }
  void Broadcast(void* buf, size_t size, int root, const char*) override {
    Must(comm_.Broadcast(buf, size, root), "broadcast");
  }
  void Allgather(void* buf, size_t total, size_t beg, size_t end,
                 const char*) override;

  int LoadCheckPoint(std::string* g, std::string* l) override {
    if (version_ > 0) {
      *g = global_;
      *l = local_;
    }
    return version_;
  }
  using Engine::CheckPoint;
  void CheckPoint(const BlobView& g, const BlobView& l) override {
    g.CopyTo(&global_);
    l.CopyTo(&local_);
    ++version_;
  }
  void LazyCheckPoint(const char* gd, size_t gl) override {
    CheckPoint(gd, gl, nullptr, 0);
  }
  int VersionNumber() const override { return version_; }
  void InitAfterException() override {
    throw Error("base engine cannot recover; use the robust engine");
  }

 protected:
  void Must(IoResult r, const char* what) {
    TRT_CHECK(r == IoResult::kOk,
              "[rank %d] peer failure during %s: the base engine is not "
              "fault-tolerant", comm_.rank(), what);
  }
  Comm comm_;
  int version_ = 0;
  std::string global_, local_;
};

// Process-wide engine singleton (the reference keeps one per thread,
// engine.cc:30-52; the engine API is not thread-safe either way).
Engine* GetEngine();
void InitEngine(int argc, char** argv);
void FinalizeEngine();

}  // namespace tpurabit
