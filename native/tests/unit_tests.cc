// White-box unit tests for the native engine substrate — the tier-1
// equivalent of the reference's test/cpp suite (SURVEY.md §4): config
// parsing (allreduce_base_test.cc), memory streams (test_io.cc), watchdog
// semantics without a cluster (allreduce_robust_test.cc), and the mock
// kill switch (allreduce_mock_test.cc).  Where the reference flips
// private->public with a macro, this binary simply #includes robust.cc to
// reach the internals.
#include <sys/socket.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <unistd.h>

#include <cstdlib>

#include "../src/robust.cc"  // white-box: Watchdog, RobustEngine, MockEngine
#include "minitest.h"

#include <tpurabit/tpurabit.h>

using namespace tpurabit;

// --- config (reference: allreduce_base_test.cc param parsing) -------------

TEST(config_args_and_units) {
  Config cfg;
  const char* argv[] = {"rabit_reduce_buffer=256M", "rabit_debug=1",
                        "rabit_task_id=worker7", "notakv"};
  cfg.LoadArgs(4, const_cast<char**>(argv));
  CHECK_EQ(cfg.Get("rabit_task_id"), "worker7");
  CHECK_EQ(cfg.GetSize("rabit_reduce_buffer"), 256u << 20);
  CHECK_TRUE(cfg.GetBool("rabit_debug"));
  CHECK_TRUE(!cfg.Has("notakv"));
}

TEST(config_unit_suffixes) {
  Config cfg;
  cfg.Set("a", "512");
  cfg.Set("b", "4K");
  cfg.Set("c", "1.5M");
  cfg.Set("d", "2G");
  cfg.Set("e", "128B");
  CHECK_EQ(cfg.GetSize("a"), 512u);
  CHECK_EQ(cfg.GetSize("b"), 4096u);
  CHECK_EQ(cfg.GetSize("c"), (size_t)(1.5 * (1 << 20)));
  CHECK_EQ(cfg.GetSize("d"), 2ull << 30);
  CHECK_EQ(cfg.GetSize("e"), 128u);
  CHECK_EQ(cfg.GetSize("missing", 77), 77u);
}

TEST(config_env_layering) {
  setenv("DMLC_TRACKER_URI", "10.0.0.1", 1);
  setenv("DMLC_TASK_ID", "3", 1);
  Config cfg;
  cfg.LoadEnv();
  CHECK_EQ(cfg.Get("rabit_tracker_uri"), "10.0.0.1");
  CHECK_EQ(cfg.Get("rabit_task_id"), "3");
  // argv overrides env (reference layering, allreduce_base.cc:49-64)
  const char* argv[] = {"rabit_task_id=9"};
  cfg.LoadArgs(1, const_cast<char**>(argv));
  CHECK_EQ(cfg.Get("rabit_task_id"), "9");
  unsetenv("DMLC_TRACKER_URI");
  unsetenv("DMLC_TASK_ID");
}

TEST(config_bool_spellings) {
  Config cfg;
  cfg.Set("t1", "1");
  cfg.Set("f1", "0");
  cfg.Set("f2", "false");
  cfg.Set("f3", "off");
  CHECK_TRUE(cfg.GetBool("t1"));
  CHECK_TRUE(!cfg.GetBool("f1"));
  CHECK_TRUE(!cfg.GetBool("f2"));
  CHECK_TRUE(!cfg.GetBool("f3"));
  CHECK_TRUE(cfg.GetBool("missing", true));
}

// --- memory streams (reference: test_io.cc) -------------------------------

TEST(memory_buffer_stream_roundtrip) {
  std::string buf;
  MemoryBufferStream w(&buf);
  int32_t a = 42;
  double b = 2.5;
  w.Write(&a, sizeof(a));
  w.Write(&b, sizeof(b));
  CHECK_EQ(buf.size(), sizeof(a) + sizeof(b));
  MemoryBufferStream r(&buf);
  int32_t a2 = 0;
  double b2 = 0;
  CHECK_EQ(r.Read(&a2, sizeof(a2)), sizeof(a2));
  CHECK_EQ(r.Read(&b2, sizeof(b2)), sizeof(b2));
  CHECK_EQ(a2, 42);
  CHECK_EQ(b2, 2.5);
  CHECK_EQ(r.Read(&a2, sizeof(a2)), 0u);  // EOF
}

TEST(memory_buffer_stream_seek) {
  std::string buf;
  MemoryBufferStream s(&buf);
  uint8_t bytes[4] = {1, 2, 3, 4};
  s.Write(bytes, 4);
  s.Seek(2);
  CHECK_EQ(s.Tell(), 2u);
  uint8_t x = 0;
  s.Read(&x, 1);
  CHECK_EQ(x, 3);
  s.Seek(0);
  uint8_t over[2] = {9, 9};
  s.Write(over, 2);
  CHECK_EQ(buf.size(), 4u);  // overwrite, no grow
}

TEST(memory_fix_size_buffer) {
  char mem[8] = {0};
  MemoryFixSizeBuffer s(mem, sizeof(mem));
  uint32_t v = 0xdeadbeef;
  s.Write(&v, sizeof(v));
  s.Seek(0);
  uint32_t v2 = 0;
  CHECK_EQ(s.Read(&v2, sizeof(v2)), sizeof(v2));
  CHECK_EQ(v2, 0xdeadbeefu);
  // reads clamp at capacity
  s.Seek(6);
  char two[4];
  CHECK_EQ(s.Read(two, 4), 2u);
}

// --- builtin reducers -----------------------------------------------------

TEST(builtin_reducers) {
  float d[3] = {1, 5, 3}, s[3] = {4, 2, 3};
  BuiltinReducer(kMax, kFloat32)(d, s, 3, nullptr);
  CHECK_EQ(d[0], 4);
  CHECK_EQ(d[1], 5);
  double dd[2] = {1, 2}, ss[2] = {3, 4};
  BuiltinReducer(kSum, kFloat64)(dd, ss, 2, nullptr);
  CHECK_EQ(dd[0], 4);
  CHECK_EQ(dd[1], 6);
  uint32_t ud[1] = {0b0101}, us[1] = {0b0011};
  BuiltinReducer(kBitOr, kUInt32)(ud, us, 1, nullptr);
  CHECK_EQ(ud[0], 0b0111u);
  // BITOR over float is invalid
  CHECK_TRUE(BuiltinReducer(kBitOr, kFloat32) == nullptr);
}

// --- watchdog (reference: allreduce_robust_test.cc timeout semantics,
// tested single-process without any cluster) ------------------------------

// --- hung-peer stall detection (round-3 liveness; the reference covered
// this blind spot with OOB CheckExcept, socket.h:440-533) ----------------

TEST(stall_timeout_reports_peer_failure) {
  int sv[2];
  CHECK_TRUE(socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
  TcpSocket a(sv[0]), b(sv[1]);
  a.SetNonBlock(true);
  char buf[16];
  // Nothing ever arrives: a wedged peer looks like an open, silent socket.
  Transfer t{a.fd(), buf, sizeof(buf), 0, /*sending=*/false};
  double t0 = NowSec();
  CHECK_TRUE(DriveTransfers(&t, 1, /*timeout_ms=*/100) ==
             IoResult::kPeerFailure);
  double dt = NowSec() - t0;
  CHECK_TRUE(dt >= 0.09 && dt < 5.0);
  (void)b;
}

// --- bounded-bootstrap primitives (round-4 liveness fix: a worker dead
// between tracker check-in and dialing must not strand accept-side
// peers; comm.cc BuildLinks builds on these two) -------------------------

TEST(wait_acceptable_times_out_and_detects_dialer) {
  TcpSocket lst;
  lst.Create();
  int port = lst.BindListen();
  double t0 = NowSec();
  CHECK_TRUE(!lst.WaitAcceptable(0.1));  // nobody dialing: bounded wait
  double dt = NowSec() - t0;
  CHECK_TRUE(dt >= 0.09 && dt < 5.0);
  TcpSocket dialer;
  dialer.Connect("127.0.0.1", port);
  CHECK_TRUE(lst.WaitAcceptable(5.0));   // pending connection: immediate
  TcpSocket s = lst.Accept();
  CHECK_TRUE(s.valid());
}

TEST(recv_timeout_bounds_silent_peer) {
  int sv[2];
  CHECK_TRUE(socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
  TcpSocket a(sv[0]), b(sv[1]);
  a.SetRecvTimeout(0.1);
  char hello[12];
  bool threw = false;
  double t0 = NowSec();
  try {
    a.RecvAll(hello, sizeof(hello));  // dialer connected, then died silent
  } catch (const Error&) {
    threw = true;
  }
  double dt = NowSec() - t0;
  CHECK_TRUE(threw);
  CHECK_TRUE(dt >= 0.09 && dt < 5.0);
  (void)b;
}

TEST(stall_timeout_progress_resets_nothing_but_completes) {
  int sv[2];
  CHECK_TRUE(socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
  TcpSocket a(sv[0]), b(sv[1]);
  a.SetNonBlock(true);
  const char msg[8] = "1234567";
  b.SendAll(msg, sizeof(msg));
  char buf[8];
  Transfer t{a.fd(), buf, sizeof(buf), 0, /*sending=*/false};
  CHECK_TRUE(DriveTransfers(&t, 1, /*timeout_ms=*/100) == IoResult::kOk);
  CHECK_TRUE(memcmp(buf, msg, sizeof(msg)) == 0);
}

TEST(watchdog_disarm_cancels) {
  Watchdog wd;
  wd.Arm(/*sec=*/5.0, /*rank=*/0);
  wd.Disarm();  // must cancel promptly and not fire later
  usleep(10 * 1000);
  CHECK_TRUE(true);
}

TEST(watchdog_zero_timeout_never_arms) {
  Watchdog wd;
  wd.Arm(/*sec=*/0.0, /*rank=*/0);
  wd.Disarm();
  CHECK_TRUE(true);
}

TEST(watchdog_fires_exit10) {
  // The armed watchdog hard-exits with code 10 (reference
  // allreduce_robust.cc:693-716 kills the process when recovery stalls
  // past rabit_timeout_sec).  Observable only from a child process.
  pid_t pid = fork();
  if (pid == 0) {
    Watchdog wd;
    wd.Arm(/*sec=*/0.05, /*rank=*/0);
    usleep(2 * 1000 * 1000);  // stall "recovery" past the bound
    _exit(0);                 // not reached
  }
  int status = 0;
  CHECK_EQ(waitpid(pid, &status, 0), pid);
  CHECK_TRUE(WIFEXITED(status));
  CHECK_EQ(WEXITSTATUS(status), 10);
}

// --- solo-mode engine through the public typed C++ API --------------------

TEST(solo_engine_full_api) {
  const char* argv[] = {"rabit_engine=empty"};
  Init(1, const_cast<char**>(argv));
  CHECK_EQ(GetRank(), 0);
  CHECK_EQ(GetWorldSize(), 1);
  CHECK_TRUE(!IsDistributed());
  CHECK_TRUE(!GetProcessorName().empty());

  int a[3] = {7, 8, 9};
  Allreduce<op::Max>(a, 3);  // world 1: identity
  CHECK_EQ(a[0], 7);

  bool prepared = false;
  Allreduce<op::Sum>(a, 3, [&]() { prepared = true; });
  CHECK_TRUE(prepared);

  std::string s = "payload";
  Broadcast(&s, 0);
  CHECK_EQ(s, "payload");

  std::vector<double> v{1.0, 2.0};
  Broadcast(&v, 0);
  CHECK_EQ(v.size(), 2u);

  Finalize();
}

// A checkpointable model for the Serializable roundtrip.
struct Model : public Serializable {
  std::vector<float> w;
  void Load(Stream* fi) override {
    uint64_t n = 0;
    fi->Read(&n, sizeof(n));
    w.resize(n);
    if (n != 0) fi->Read(w.data(), n * sizeof(float));
  }
  void Save(Stream* fo) const override {
    uint64_t n = w.size();
    fo->Write(&n, sizeof(n));
    if (n != 0) fo->Write(w.data(), n * sizeof(float));
  }
};

TEST(solo_checkpoint_roundtrip) {
  const char* argv[] = {"rabit_engine=empty"};
  Init(1, const_cast<char**>(argv));
  Model m;
  CHECK_EQ(LoadCheckPoint(&m), 0);  // nothing checkpointed yet
  CHECK_EQ(VersionNumber(), 0);
  m.w = {1.5f, -2.0f, 3.25f};
  CheckPoint(&m);
  CHECK_EQ(VersionNumber(), 1);
  Model m2;
  CHECK_EQ(LoadCheckPoint(&m2), 1);
  CHECK_EQ(m2.w.size(), 3u);
  CHECK_EQ(m2.w[2], 3.25f);
  // lazy variant bumps version too
  LazyCheckPoint(&m);
  CHECK_EQ(VersionNumber(), 2);
  Finalize();
}

TEST(checkpoint_pieces_are_joined_and_not_kept) {
  // The gather entry: the engine's copy is the pieces in order, whatever
  // the caller does to its memory afterwards; no local piece, or none with
  // a byte in it, is no local model (as RabitCheckPoint's local_len == 0).
  for (const char* kind : {"rabit_engine=empty", "rabit_engine=robust"}) {
    const char* argv[] = {kind};
    Init(1, const_cast<char**>(argv));
    std::string head = "head|", body(100000, 'b'), tail = "|tail";
    TrtBlobPiece g[3] = {{head.data(), head.size()},
                         {body.data(), body.size()},
                         {tail.data(), tail.size()}};
    TrtBlobPiece l[2] = {{"", 0}, {"local", 5}};
    CHECK_EQ(TrtCheckPointPieces(g, 3, l, 2), 0);
    body.assign(body.size(), 'x');  // the caller's array, overwritten at once
    char *gp = nullptr, *lp = nullptr;
    trt_ulong gn = 0, ln = 0;
    CHECK_EQ(RabitLoadCheckPoint(&gp, &gn, &lp, &ln), 1);
    CHECK_TRUE(std::string(gp, gn) ==
               "head|" + std::string(100000, 'b') + "|tail");
    CHECK_TRUE(std::string(lp, ln) == "local");
    TrtBlobPiece empty = {"", 0};
    CHECK_EQ(TrtCheckPointPieces(g, 1, &empty, 1), 0);
    CHECK_EQ(RabitLoadCheckPoint(&gp, &gn, &lp, &ln), 2);
    CHECK_TRUE(std::string(gp, gn) == "head|");
    CHECK_EQ(TrtCheckPointPieces(g, 1, nullptr, 0), 0);
    CHECK_EQ(VersionNumber(), 3);
    Finalize();
  }
}

struct Pair {
  double sum;
  int64_t n;
};
static void MergePair(Pair& d, const Pair& s) {
  d.sum += s.sum;
  d.n += s.n;
}

TEST(solo_custom_reducer) {
  const char* argv[] = {"rabit_engine=empty"};
  Init(1, const_cast<char**>(argv));
  Pair p{3.5, 2};
  Reducer<Pair, MergePair> red;
  red.Allreduce(&p, 1);
  CHECK_EQ(p.sum, 3.5);
  CHECK_EQ(p.n, 2);
  Finalize();
}

// SerializeReducer: world-1 path still serializes + deserializes in place,
// so the Save/Load/Reduce contract is exercised.
struct Sketch {
  std::vector<int32_t> items;
  void Load(Stream* fi) {
    uint64_t n = 0;
    fi->Read(&n, sizeof(n));
    items.resize(n);
    if (n != 0) fi->Read(items.data(), n * sizeof(int32_t));
  }
  void Save(Stream* fo) const {
    uint64_t n = items.size();
    fo->Write(&n, sizeof(n));
    if (n != 0) fo->Write(items.data(), n * sizeof(int32_t));
  }
  void Reduce(const Sketch& src, size_t) {
    items.insert(items.end(), src.items.begin(), src.items.end());
  }
};

TEST(solo_serialize_reducer) {
  const char* argv[] = {"rabit_engine=empty"};
  Init(1, const_cast<char**>(argv));
  Sketch sk;
  sk.items = {4, 5};
  SerializeReducer<Sketch> red;
  red.Allreduce(&sk, /*max_nbyte=*/64, /*count=*/1);
  CHECK_EQ(sk.items.size(), 2u);
  CHECK_EQ(sk.items[1], 5);
  Finalize();
}

// --- mock kill switch (reference: allreduce_mock_test.cc) -----------------

TEST(mock_kill_fires_at_exact_point) {
  // Solo mock engine (seqno stays 0 solo, like the reference's world==1
  // fast path): kill spec addresses version 1, so ops before the first
  // checkpoint run fine and the first op after it must throw.
  MockEngine eng;
  Config cfg;
  const char* argv[] = {"mock=0,1,0,0"};
  cfg.LoadArgs(1, const_cast<char**>(argv));
  eng.Init(cfg);
  float x[2] = {1, 2};
  eng.Allreduce(x, sizeof(float), 2, BuiltinReducer(kSum, kFloat32), nullptr,
                nullptr, nullptr, "");  // version 0: fine
  eng.CheckPoint("m", 1, nullptr, 0);   // -> version 1
  CHECK_THROWS(eng.Allreduce(x, sizeof(float), 2,
                             BuiltinReducer(kSum, kFloat32), nullptr, nullptr,
                             nullptr, ""));  // version 1: boom
}

TEST(mock_kill_respects_trial) {
  // trial=1 means "second life": with rabit_num_trial=0 nothing fires.
  MockEngine eng;
  Config cfg;
  const char* argv[] = {"mock=0,0,0,1"};
  cfg.LoadArgs(1, const_cast<char**>(argv));
  eng.Init(cfg);
  float x[1] = {0};
  eng.Allreduce(x, sizeof(float), 1, BuiltinReducer(kSum, kFloat32), nullptr,
                nullptr, nullptr, "");
  CHECK_TRUE(true);
}

int main() { return minitest::RunAll(); }
