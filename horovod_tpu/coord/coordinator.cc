// hvdcoord — host coordination core for horovod_tpu.
//
// TPU-native analog of the reference's native runtime
// (horovod/tensorflow/mpi_ops.cc): a rank-0 coordinator counts name-keyed
// collective announcements from every rank, validates them across ranks with
// the same error classification (ConstructMPIResponse, mpi_ops.cc:266-474), detects
// stalls (CheckForStalledTensors, mpi_ops.cc:1153-1196), plans tensor fusion
// (mpi_ops.cc:1395-1422) and executes the *eager host data plane* — the
// op-at-a-time collectives issued outside compiled XLA programs (metric
// averaging, epoch broadcast, init-time weight sync). The compiled data plane
// (gradient psum over ICI) never touches this code; XLA schedules it.
//
// Transport: length-prefixed binary messages over TCP (DCN stand-in) in a
// star topology — every rank (including 0) connects as a client to the
// coordinator server thread. This replaces the reference's
// MPI_Send/Probe/Recv of FlatBuffers (mpi_ops.cc:1319-1374); the message
// *content* is the same information, the wire format is our own.
//
// Threading model mirrors the reference's single-owner discipline
// (SURVEY §5.2): all coordinator state is owned by the server thread; each
// client has a receiver thread feeding a completed-op map guarded by one
// mutex + condvar; enqueue serializes sends with a socket mutex.

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <chrono>
#include <memory>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <set>
#include <sstream>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace hvdcoord {

// ---------------------------------------------------------------------------
// Protocol constants (values are wire ABI; keep stable).
// ---------------------------------------------------------------------------

enum class ReqType : uint8_t {
  kAllreduce = 0,
  kAllgather = 1,
  kBroadcast = 2,
  // TPU-era extras (compiled-plane parity: ops/collectives.py alltoall /
  // reducescatter; not in reference v0.11.2).
  kAlltoall = 3,
  kReducescatter = 4,
  // Large-payload allreduce announced WITHOUT its payload: the data plane
  // is a client-to-client chunked ring (reduce-scatter + allgather), the
  // bandwidth-optimal algorithm the reference gets from MPI_Allreduce
  // (mpi_ops.cc:1061-1064 — every real MPI rings large messages). The
  // coordinator only negotiates/validates and ships the ring plan; payload
  // bytes never transit rank 0, so per-rank traffic is 2·(N-1)/N · bytes
  // independent of world size (vs the star's N·bytes coordinator
  // ingress/egress).
  kAllreduceRing = 5,
  // Large allgather on the same ring plane: each rank's block circulates
  // N-1 hops, so per-rank traffic is ~(output - own block) — the star
  // would push N x output through the coordinator's egress. Ragged first
  // dims ride the same negotiated sizes the star allgather uses (the
  // reference's MPI_Allgatherv ring, mpi_ops.cc:788-808).
  kAllgatherRing = 6,
  // Large broadcast (root-elected): chunk-pipelined chain from the root.
  kBroadcastRing = 7,
  // Large alltoall on the peer data plane: direct pairwise block exchange
  // over the full-duplex peer-socket mesh (every rank sends N-1 blocks
  // straight to their destinations), so per-rank traffic is
  // (N-1)/N · payload independent of world size — the star would relay
  // N · payload through rank 0 in each direction.
  kAlltoallRing = 8,
  // Large reducescatter: the reduce-scatter PHASE of the ring allreduce
  // alone (each rank ends owning its fully-reduced block); per-rank
  // traffic (N-1)/N · payload, again world-size independent.
  kReducescatterRing = 9,
};
enum class RespType : uint8_t {
  kAllreduce = 0,
  kAllgather = 1,
  kBroadcast = 2,
  kError = 3,
  kShutdown = 4,
  kAlltoall = 5,
  kReducescatter = 6,
  kAllreduceRing = 7,  // carries the ring plan (peer addresses), no payload
  kAllgatherRing = 8,  // ring plan + negotiated per-rank first dims
  // Ragged allgathers can legitimately STRADDLE the ring threshold (some
  // ranks' blocks above it, some below — no config skew involved). The
  // coordinator resolves the mix by asking the ring announcers to
  // resubmit with their payload (one extra round trip, mixed case only).
  kResubmitStar = 9,
  // Large broadcast over the ring as a chunk-pipelined CHAIN from the
  // root: per-link traffic is exactly the payload (the star's
  // coordinator egress is N x payload) — the bandwidth model inside
  // MPI_Bcast (mpi_ops.cc:1134-1136). Only the ROOT elects (it alone
  // ships payload); non-roots follow the plan.
  kBroadcastRing = 10,
  kAlltoallRing = 11,       // mesh plan: direct pairwise block exchange
  kReducescatterRing = 12,  // ring plan: reduce-scatter phase only
  // World abort (v6): a rank died (socket closed without a clean shutdown)
  // or went silent past HVD_HEARTBEAT_TIMEOUT. Broadcast to every
  // surviving rank so every blocked hvdcoord_wait fails fast with the dead
  // rank's identity (-> Python WorkerFailureError) instead of hanging.
  kAbort = 13,
  // Pending live resize (v7): pushed to every rank the moment an admin
  // resize request is accepted (sizes = {target_world, new_coord_port,
  // generation}); also piggybacked on every heartbeat ack. Purely
  // advisory — ranks act on it at their next step boundary
  // (horovod_tpu.elastic.ResizeCoordinator), never mid-collective.
  kResizeNotice = 14,
};

// Reduction op for allreduce/reducescatter. The reference supports SUM only
// (MPI_SUM, mpi_ops.cc:1061-1064); MIN/MAX/PROD close the asymmetry with the
// compiled plane's Op enum (average = SUM + client-side divide).
enum class RedOp : uint8_t { kSum = 0, kMin = 1, kMax = 2, kProd = 3 };

const char* RedOpName(RedOp o) {
  switch (o) {
    case RedOp::kSum: return "SUM";
    case RedOp::kMin: return "MIN";
    case RedOp::kMax: return "MAX";
    case RedOp::kProd: return "PRODUCT";
  }
  return "UNKNOWN";
}

// Dtypes: the reference's nine (mpi_message.h:26-36) plus bfloat16 (TPU era).
enum class DType : uint8_t {
  kU8 = 0, kI8 = 1, kU16 = 2, kI16 = 3, kI32 = 4, kI64 = 5,
  kF32 = 6, kF64 = 7, kBool = 8, kBF16 = 9,
};

const char* DTypeName(DType t) {
  switch (t) {
    case DType::kU8: return "uint8";
    case DType::kI8: return "int8";
    case DType::kU16: return "uint16";
    case DType::kI16: return "int16";
    case DType::kI32: return "int32";
    case DType::kI64: return "int64";
    case DType::kF32: return "float32";
    case DType::kF64: return "float64";
    case DType::kBool: return "bool";
    case DType::kBF16: return "bfloat16";
  }
  return "unknown";
}

const char* ReqTypeName(ReqType t) {
  switch (t) {
    case ReqType::kAllreduce: return "ALLREDUCE";
    case ReqType::kAllgather: return "ALLGATHER";
    case ReqType::kBroadcast: return "BROADCAST";
    case ReqType::kAlltoall: return "ALLTOALL";
    case ReqType::kReducescatter: return "REDUCESCATTER";
    // Distinct names so a mixed star/ring announcement (skewed
    // HOROVOD_RING_THRESHOLD across ranks) produces a self-explaining
    // mismatch error.
    case ReqType::kAllreduceRing: return "ALLREDUCE_RING";
    case ReqType::kAllgatherRing: return "ALLGATHER_RING";
    case ReqType::kBroadcastRing: return "BROADCAST_RING";
    case ReqType::kAlltoallRing: return "ALLTOALL_RING";
    case ReqType::kReducescatterRing: return "REDUCESCATTER_RING";
  }
  return "UNKNOWN";
}

// Defense-in-depth for direct/nonconforming clients: a request whose type
// byte is outside the known enum must become a NAMED validation error, not
// fall through response-construction switches into a default-initialized
// Response (protocol-version checks already reject mixed builds at hello).
bool KnownReqType(ReqType t) {
  return static_cast<uint8_t>(t) <=
         static_cast<uint8_t>(ReqType::kReducescatterRing);
}

int DTypeSize(DType t) {
  switch (t) {
    case DType::kU8: case DType::kI8: case DType::kBool: return 1;
    case DType::kU16: case DType::kI16: case DType::kBF16: return 2;
    case DType::kI32: case DType::kF32: return 4;
    case DType::kI64: case DType::kF64: return 8;
  }
  return 1;
}

// ---------------------------------------------------------------------------
// Wire helpers: length-prefixed frames of {u8 tag, payload}.
// ---------------------------------------------------------------------------

enum class MsgTag : uint8_t {
  kRequest = 1,
  kResponse = 2,
  kShutdown = 3,
  kHelloAck = 4,
  // Liveness plane (v6): clients beat every ~HVD_HEARTBEAT_TIMEOUT/4; the
  // coordinator acks each beat. Either side going silent past the timeout
  // is a worker/coordinator failure, not a stall — the world ABORTS
  // (RespType::kAbort) instead of hanging, the failure mode the reference
  // inherits from MPI (a dead rank wedges MPI_Allreduce forever;
  // CheckForStalledTensors only *warns*, mpi_ops.cc:1153-1196).
  kHeartbeat = 5,
  kHeartbeatAck = 6,
  // Admin plane (v7): an operator (or the supervising tpurun) connects to
  // the coordinator port AFTER world formation and requests a live resize
  // of the world — the Elastic-Horovod "host discovery" role, inverted:
  // instead of the launcher polling a discovery script, the resize intent
  // is pushed into the running world through the plane that already talks
  // to every rank. kResizeRequest{target} with target=0 is a pure status
  // query (world size + pending resize), used by tpurun's supervision
  // loop to learn when it must spawn new ranks.
  kResizeRequest = 7,
  kResizeReply = 8,
};

// Wire protocol version; bumped on incompatible frame-layout changes. Both
// sides are built from this one source so a mismatch means two ranks loaded
// different builds — exactly the cross-rank config skew init must reject
// (the analog of the reference's per-tensor placement validation,
// mpi_ops.cc:439-449, moved to init time where TPU worlds can check it).
// v5: ring election extended to alltoall/reducescatter; hello may carry an
// advertise-address suffix (HOROVOD_RING_ADVERTISE_ADDR).
// v6: liveness plane — kHeartbeat/kHeartbeatAck frames and the kAbort
// response (fail-fast worker-failure detection, HVD_HEARTBEAT_TIMEOUT).
// v7: live-resize plane — post-formation admin connections
// (kResizeRequest/kResizeReply), the kResizeNotice push, and the pending-
// resize payload appended to every kHeartbeatAck (ranks learn of a pending
// resize at a step boundary with ZERO extra collectives on the hot path).
constexpr int32_t kProtocolVersion = 7;

// ---------------------------------------------------------------------------
// Env parsing. atoll/atof would silently truncate ("4M" -> 4) or zero out
// garbage, degrading performance with no diagnostic; reject trailing
// characters loudly and keep the default instead.
// ---------------------------------------------------------------------------

long long ParseEnvI64(const char* name, long long dflt,
                      bool* parsed_ok = nullptr) {
  if (parsed_ok) *parsed_ok = false;
  const char* v = getenv(name);
  if (!v || !*v) return dflt;
  char* end = nullptr;
  errno = 0;
  long long out = strtoll(v, &end, 10);
  if (end == v || *end != '\0' || errno == ERANGE) {
    fprintf(stderr,
            "hvdcoord: ignoring malformed %s=\"%s\" (expected a plain "
            "integer; size suffixes like \"4M\" are not supported) — "
            "using default %lld\n",
            name, v, dflt);
    return dflt;
  }
  if (parsed_ok) *parsed_ok = true;
  return out;
}

double ParseEnvF64(const char* name, double dflt) {
  const char* v = getenv(name);
  if (!v || !*v) return dflt;
  char* end = nullptr;
  errno = 0;
  double out = strtod(v, &end);
  if (end == v || *end != '\0' || errno == ERANGE) {
    fprintf(stderr,
            "hvdcoord: ignoring malformed %s=\"%s\" (expected a plain "
            "number) — using default %g\n",
            name, v, dflt);
    return dflt;
  }
  return out;
}

struct Request {
  int32_t rank = -1;
  ReqType type = ReqType::kAllreduce;
  DType dtype = DType::kF32;
  RedOp red_op = RedOp::kSum;
  int32_t root_rank = -1;
  std::vector<int64_t> shape;
  std::string name;
  std::string payload;  // tensor bytes (empty for non-root broadcast)
};

struct Response {
  RespType type = RespType::kAllreduce;
  std::string name;
  std::string error;
  std::vector<int64_t> sizes;  // allgather: per-rank first dims
  std::string payload;         // result bytes
  // Fusion (reference: MPIResponse.tensor_names[] >1 entries => fused,
  // mpi_message.h:94-139; decision mpi_ops.cc:1395-1422): a fused response
  // carries the concatenated results of several same-dtype allreduces in one
  // frame; the client splits by per-name byte counts.
  std::vector<std::string> fused_names;
  std::vector<int64_t> fused_nbytes;
  // Ring plan (kAllreduceRing): "ip:port" peer data-plane addresses indexed
  // by rank; clients run the chunked ring among themselves.
  std::vector<std::string> ring_peers;
  // dtype: on the wire for ring plans (sizes non-root broadcast
  // buffers); otherwise coordinator-local bookkeeping.
  DType dtype = DType::kF32;
  std::vector<int64_t> shape;                 // output shape (timeline args)
  std::vector<std::string> per_rank_payloads; // alltoall/reducescatter
};

class Buf {
 public:
  void PutU8(uint8_t v) { data_.push_back(static_cast<char>(v)); }
  void PutI32(int32_t v) { Raw(&v, 4); }
  void PutI64(int64_t v) { Raw(&v, 8); }
  void PutStr(const std::string& s) {
    PutI64(static_cast<int64_t>(s.size()));
    data_.append(s);
  }
  void Raw(const void* p, size_t n) {
    data_.append(reinterpret_cast<const char*>(p), n);
  }
  const std::string& str() const { return data_; }

 private:
  std::string data_;
};

class Reader {
 public:
  explicit Reader(const std::string& d) : d_(d) {}
  uint8_t GetU8() { return static_cast<uint8_t>(d_[off_++]); }
  int32_t GetI32() { int32_t v; memcpy(&v, d_.data() + off_, 4); off_ += 4; return v; }
  int64_t GetI64() { int64_t v; memcpy(&v, d_.data() + off_, 8); off_ += 8; return v; }
  std::string GetStr() {
    int64_t n = GetI64();
    std::string s = d_.substr(off_, n);
    off_ += n;
    return s;
  }

 private:
  const std::string& d_;
  size_t off_ = 0;
};

std::string EncodeRequest(const Request& r) {
  Buf b;
  b.PutU8(static_cast<uint8_t>(MsgTag::kRequest));
  b.PutI32(r.rank);
  b.PutU8(static_cast<uint8_t>(r.type));
  b.PutU8(static_cast<uint8_t>(r.dtype));
  b.PutU8(static_cast<uint8_t>(r.red_op));
  b.PutI32(r.root_rank);
  b.PutU8(static_cast<uint8_t>(r.shape.size()));
  for (int64_t d : r.shape) b.PutI64(d);
  b.PutStr(r.name);
  b.PutStr(r.payload);
  return b.str();
}

Request DecodeRequest(Reader& rd) {
  Request r;
  r.rank = rd.GetI32();
  r.type = static_cast<ReqType>(rd.GetU8());
  r.dtype = static_cast<DType>(rd.GetU8());
  r.red_op = static_cast<RedOp>(rd.GetU8());
  r.root_rank = rd.GetI32();
  int nd = rd.GetU8();
  for (int i = 0; i < nd; i++) r.shape.push_back(rd.GetI64());
  r.name = rd.GetStr();
  r.payload = rd.GetStr();
  return r;
}

std::string EncodeResponse(const Response& r) {
  Buf b;
  b.PutU8(static_cast<uint8_t>(MsgTag::kResponse));
  b.PutU8(static_cast<uint8_t>(r.type));
  b.PutStr(r.name);
  b.PutStr(r.error);
  b.PutI32(static_cast<int32_t>(r.sizes.size()));
  for (int64_t s : r.sizes) b.PutI64(s);
  b.PutI32(static_cast<int32_t>(r.fused_names.size()));
  for (size_t i = 0; i < r.fused_names.size(); i++) {
    b.PutStr(r.fused_names[i]);
    b.PutI64(r.fused_nbytes[i]);
  }
  b.PutI32(static_cast<int32_t>(r.ring_peers.size()));
  for (const auto& p : r.ring_peers) b.PutStr(p);
  // dtype AND shape ride the wire for ring PLANS: a non-root broadcast
  // client has no stash, so the plan itself must size the receive buffer
  // (shape was coordinator-local before v5 — the r3 chain sized non-root
  // buffers from an empty shape).
  b.PutU8(static_cast<uint8_t>(r.dtype));
  b.PutU8(static_cast<uint8_t>(r.shape.size()));
  for (int64_t d : r.shape) b.PutI64(d);
  b.PutStr(r.payload);
  return b.str();
}

Response DecodeResponse(Reader& rd) {
  Response r;
  r.type = static_cast<RespType>(rd.GetU8());
  r.name = rd.GetStr();
  r.error = rd.GetStr();
  int n = rd.GetI32();
  for (int i = 0; i < n; i++) r.sizes.push_back(rd.GetI64());
  int nf = rd.GetI32();
  for (int i = 0; i < nf; i++) {
    r.fused_names.push_back(rd.GetStr());
    r.fused_nbytes.push_back(rd.GetI64());
  }
  int np = rd.GetI32();
  for (int i = 0; i < np; i++) r.ring_peers.push_back(rd.GetStr());
  r.dtype = static_cast<DType>(rd.GetU8());
  int nd = rd.GetU8();
  for (int i = 0; i < nd; i++) r.shape.push_back(rd.GetI64());
  r.payload = rd.GetStr();
  return r;
}

// Framed socket IO. Returns false on EOF/error.
bool SendFrame(int fd, std::mutex& mu, const std::string& body) {
  std::lock_guard<std::mutex> l(mu);
  uint64_t len = body.size();
  std::string frame(reinterpret_cast<char*>(&len), 8);
  frame += body;
  size_t off = 0;
  while (off < frame.size()) {
    ssize_t n = ::send(fd, frame.data() + off, frame.size() - off, MSG_NOSIGNAL);
    if (n <= 0) return false;
    off += static_cast<size_t>(n);
  }
  return true;
}

bool RecvAll(int fd, void* p, size_t n) {
  size_t off = 0;
  while (off < n) {
    ssize_t r = ::recv(fd, reinterpret_cast<char*>(p) + off, n - off, 0);
    if (r <= 0) return false;
    off += static_cast<size_t>(r);
  }
  return true;
}

// Frames above this are protocol violations (a stray/hostile connection
// sending a garbage 64-bit length must not trigger a std::bad_alloc that
// terminates the coordinator); 16 GiB comfortably exceeds any real tensor
// the host eager plane carries.
constexpr uint64_t kMaxFrameBytes = 1ull << 34;

bool RecvFrame(int fd, std::string* body) {
  uint64_t len;
  if (!RecvAll(fd, &len, 8)) return false;
  if (len > kMaxFrameBytes) return false;
  body->resize(len);
  return len == 0 || RecvAll(fd, &(*body)[0], len);
}

// ---------------------------------------------------------------------------
// Reduction kernels (host eager plane; SUM like the reference's MPI_SUM path,
// mpi_ops.cc:1061-1064).
// ---------------------------------------------------------------------------

template <typename T>
void ReduceIntoRaw(RedOp op, char* acc, const char* in, size_t nbytes) {
  T* a = reinterpret_cast<T*>(acc);
  const T* b = reinterpret_cast<const T*>(in);
  size_t n = nbytes / sizeof(T);
  switch (op) {
    case RedOp::kSum:
      for (size_t i = 0; i < n; i++) a[i] += b[i];
      return;
    case RedOp::kMin:
      for (size_t i = 0; i < n; i++) a[i] = std::min(a[i], b[i]);
      return;
    case RedOp::kMax:
      for (size_t i = 0; i < n; i++) a[i] = std::max(a[i], b[i]);
      return;
    case RedOp::kProd:
      for (size_t i = 0; i < n; i++) a[i] *= b[i];
      return;
  }
}

// bfloat16: widen to float, reduce, narrow (round-to-nearest-even).
void ReduceIntoBF16(RedOp op, char* accp, const char* inp, size_t nbytes) {
  uint16_t* a = reinterpret_cast<uint16_t*>(accp);
  const uint16_t* b = reinterpret_cast<const uint16_t*>(inp);
  size_t n = nbytes / 2;
  for (size_t i = 0; i < n; i++) {
    uint32_t av = static_cast<uint32_t>(a[i]) << 16;
    uint32_t bv = static_cast<uint32_t>(b[i]) << 16;
    float af, bf;
    memcpy(&af, &av, 4);
    memcpy(&bf, &bv, 4);
    switch (op) {
      case RedOp::kSum: af += bf; break;
      case RedOp::kMin: af = std::min(af, bf); break;
      case RedOp::kMax: af = std::max(af, bf); break;
      case RedOp::kProd: af *= bf; break;
    }
    uint32_t out;
    memcpy(&out, &af, 4);
    // round-to-nearest-even on the dropped 16 bits
    uint32_t rounded = out + 0x7FFF + ((out >> 16) & 1);
    a[i] = static_cast<uint16_t>(rounded >> 16);
  }
}

void ReducePayloadRaw(DType t, RedOp op, char* acc, const char* in,
                      size_t nbytes) {
  switch (t) {
    case DType::kU8: return ReduceIntoRaw<uint8_t>(op, acc, in, nbytes);
    case DType::kI8: return ReduceIntoRaw<int8_t>(op, acc, in, nbytes);
    case DType::kU16: return ReduceIntoRaw<uint16_t>(op, acc, in, nbytes);
    case DType::kI16: return ReduceIntoRaw<int16_t>(op, acc, in, nbytes);
    case DType::kI32: return ReduceIntoRaw<int32_t>(op, acc, in, nbytes);
    case DType::kI64: return ReduceIntoRaw<int64_t>(op, acc, in, nbytes);
    case DType::kF32: return ReduceIntoRaw<float>(op, acc, in, nbytes);
    case DType::kF64: return ReduceIntoRaw<double>(op, acc, in, nbytes);
    case DType::kBool: {
      // bool: SUM/MAX = logical OR, MIN/PROD = logical AND (the lattice
      // forms the reference's MPI byte-sum reduces to for 0/1 values).
      uint8_t* a = reinterpret_cast<uint8_t*>(acc);
      const uint8_t* b = reinterpret_cast<const uint8_t*>(in);
      bool is_or = (op == RedOp::kSum || op == RedOp::kMax);
      for (size_t i = 0; i < nbytes; i++)
        a[i] = is_or ? (a[i] || b[i]) : (a[i] && b[i]);
      return;
    }
    case DType::kBF16: return ReduceIntoBF16(op, acc, in, nbytes);
  }
}

void ReducePayload(DType t, RedOp op, std::string* acc, const std::string& in) {
  ReducePayloadRaw(t, op, &(*acc)[0], in.data(), in.size());
}

// Reduce every announced payload (requests[1..n)) into *acc, striping the
// byte range across a few threads for large tensors: the coordinator's
// host reduction is O(size · bytes) on one thread otherwise — fine on the
// reference's per-rank design (each rank reduces its own ops), but here
// rank 0 does the whole world's star-plane work (VERDICT r3 weak #4).
// Stripes are element-aligned; each thread walks all ranks within its
// range (one pass through cache per stripe). Engaged only for >=256 KiB
// payloads and >1 available core; HOROVOD_COORD_REDUCE_THREADS overrides
// the thread count (0/1 forces the serial path — also how tests exercise
// the striped path on a 1-core host by setting it >1).
void ReduceAllStriped(DType t, RedOp op, std::string* acc,
                      const std::vector<Request>& requests) {
  const size_t nbytes = acc->size();
  static bool env_parsed = false;
  static const long long kThreads = [] {
    long long v = ParseEnvI64("HOROVOD_COORD_REDUCE_THREADS",
                              std::thread::hardware_concurrency(),
                              &env_parsed);
    return v < 0 ? 0 : v;
  }();
  const size_t esz = static_cast<size_t>(DTypeSize(t));
  // Default (env unset): up to 4 stripes — past that the reduce is memory
  // -bandwidth bound on most hosts. An EXPLICIT override is honored up to
  // 16 (clamped loudly; silent caps hide why raising the knob stops
  // helping).
  // "Explicit" = set AND parseable (parsed_ok from the shared parser): a
  // malformed value falls back to ParseEnvI64's default
  // (hardware_concurrency) and must then also get the default 4-stripe
  // cap, or the "using default" warning would lie.
  long long want = env_parsed ? kThreads : std::min<long long>(kThreads, 4);
  if (want > 16) {
    static std::atomic<bool> warned{false};
    if (!warned.exchange(true))
      fprintf(stderr,
              "hvdcoord: HOROVOD_COORD_REDUCE_THREADS=%lld clamped to 16 "
              "(stripe cap)\n", want);
    want = 16;
  }
  int stripes = (nbytes >= (256u << 10) && want > 1)
                    ? static_cast<int>(want)
                    : 1;
  if (stripes <= 1) {
    for (size_t r = 1; r < requests.size(); r++)
      ReducePayload(t, op, acc, requests[r].payload);
    return;
  }
  const size_t elems = nbytes / esz;
  std::vector<std::thread> ts;
  ts.reserve(stripes);
  for (int s = 0; s < stripes; s++) {
    const size_t lo = elems * s / stripes * esz;
    const size_t hi = elems * (s + 1) / stripes * esz;
    ts.emplace_back([&, lo, hi] {
      for (size_t r = 1; r < requests.size(); r++)
        ReducePayloadRaw(t, op, &(*acc)[lo],
                         requests[r].payload.data() + lo, hi - lo);
    });
  }
  for (auto& th : ts) th.join();
}

// ---------------------------------------------------------------------------
// Chrome-trace timeline (reference: timeline.cc; doc docs/timeline.md).
// Written by the coordinator only, covering every rank's readiness.
// ---------------------------------------------------------------------------

class Timeline {
 public:
  void Open(const std::string& path) {
    f_ = fopen(path.c_str(), "w");
    if (f_) fputs("[\n", f_);
    start_ = Now();
  }
  ~Timeline() { Close(); }
  void Close() {
    if (f_) {
      fputs("{}]\n", f_);
      fclose(f_);
      f_ = nullptr;
    }
  }
  bool enabled() const { return f_ != nullptr; }

  int64_t Now() {
    return std::chrono::duration_cast<std::chrono::microseconds>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
  }

  int Pid(const std::string& name) {
    auto it = pids_.find(name);
    if (it != pids_.end()) return it->second;
    int pid = static_cast<int>(pids_.size()) + 1;
    pids_[name] = pid;
    fprintf(f_,
            "{\"name\":\"process_name\",\"ph\":\"M\",\"pid\":%d,"
            "\"args\":{\"name\":\"%s\"}},\n", pid, name.c_str());
    return pid;
  }

  // args_json, when non-empty, is a preformatted JSON object attached to the
  // event (the reference's End logs output dtype+shape, timeline.cc:203-220).
  // Every event carries tid 0: Perfetto and some catapult builds need a tid
  // to pair B/E durations within a pid.
  void Event(const std::string& name, const char* ph, const char* ev,
             const std::string& args_json = "") {
    if (!f_) return;
    std::lock_guard<std::mutex> l(mu_);
    if (args_json.empty()) {
      fprintf(f_,
              "{\"name\":\"%s\",\"ph\":\"%s\",\"pid\":%d,\"tid\":0,"
              "\"ts\":%lld},\n",
              ev, ph, Pid(name), static_cast<long long>(Now() - start_));
    } else {
      fprintf(f_,
              "{\"name\":\"%s\",\"ph\":\"%s\",\"pid\":%d,\"tid\":0,"
              "\"ts\":%lld,\"args\":%s},\n",
              ev, ph, Pid(name), static_cast<long long>(Now() - start_),
              args_json.c_str());
    }
    fflush(f_);
  }

  // Typed transitions enforcing the reference's per-tensor state machine
  // UNKNOWN→NEGOTIATING→TOP_LEVEL→ACTIVITY (timeline.h:37-42, asserted in
  // timeline.cc:118-135). A call out of order aborts: an unbalanced B/E
  // stream corrupts the whole trace, so misuse must fail loudly. All typed
  // calls happen on the coordinator thread; states_ needs no lock.
  void NegotiateStart(const std::string& name, const char* op) {
    Expect(name, State::kUnknown, "NegotiateStart");
    states_[name] = {State::kNegotiating, 0};
    Event(name, "B", (std::string("NEGOTIATE_") + op).c_str());
  }
  void NegotiateRankReady(const std::string& name, int rank) {
    Expect(name, State::kNegotiating, "NegotiateRankReady");
    std::ostringstream ev;
    ev << "rank_" << rank << "_ready";
    Event(name, "i", ev.str().c_str());
  }
  void NegotiateEnd(const std::string& name, const char* op) {
    Expect(name, State::kNegotiating, "NegotiateEnd");
    states_[name] = {State::kUnknown, 0};
    Event(name, "E", (std::string("NEGOTIATE_") + op).c_str());
  }
  void Start(const std::string& name, const char* op) {
    Expect(name, State::kUnknown, "Start");
    states_[name] = {State::kTopLevel, 0};
    Event(name, "B", op);
  }
  void ActivityStart(const std::string& name, const char* act) {
    auto& st = states_[name];
    if (st.s != State::kTopLevel && st.s != State::kActivity)
      Violate(name, "ActivityStart");
    st.s = State::kActivity;
    st.depth++;
    Event(name, "B", act);
  }
  void ActivityEnd(const std::string& name, const char* act) {
    auto& st = states_[name];
    if (st.s != State::kActivity) Violate(name, "ActivityEnd");
    st.depth--;
    if (st.depth == 0) st.s = State::kTopLevel;
    Event(name, "E", act);
  }
  void End(const std::string& name, const std::string& args_json = "") {
    Expect(name, State::kTopLevel, "End");
    states_.erase(name);
    Event(name, "E", "", args_json);
  }

 private:
  enum class State { kUnknown, kNegotiating, kTopLevel, kActivity };
  struct TState {
    State s = State::kUnknown;
    int depth = 0;
  };

  void Violate(const std::string& name, const char* call) {
    fprintf(stderr, "[hvdcoord] timeline state violation: %s(%s)\n", call,
            name.c_str());
    abort();
  }
  void Expect(const std::string& name, State want, const char* call) {
    auto it = states_.find(name);
    State s = it == states_.end() ? State::kUnknown : it->second.s;
    if (s != want) Violate(name, call);
  }

  FILE* f_ = nullptr;
  int64_t start_ = 0;
  std::mutex mu_;
  std::unordered_map<std::string, int> pids_;
  std::unordered_map<std::string, TState> states_;
};

// ---------------------------------------------------------------------------
// Coordinator (rank-0 server thread).
// ---------------------------------------------------------------------------

struct PendingTensor {
  std::vector<Request> requests;   // one per announced rank
  std::vector<bool> announced;     // by rank
  std::chrono::steady_clock::time_point first_seen;
  int count = 0;
};

// Whether a hello's ring advertise-address suffix is a well-formed IPv4
// literal ("a.b.c.d" or "a.b.c.d:port", port 1-65535). Conforming clients
// validate HOROVOD_RING_ADVERTISE_ADDR before sending it (Client::Hello
// below); the coordinator re-validates at hello so a NONconforming
// client's garbage address gets a named hello rejection HERE instead of
// being distributed in ring plans and surfacing one op later as connector
// failures on OTHER ranks.
static bool ValidAdvertiseAddr(const std::string& a) {
  size_t colon = a.find(':');
  std::string ip = a.substr(0, colon);
  in_addr probe{};
  if (ip.empty() || inet_pton(AF_INET, ip.c_str(), &probe) != 1)
    return false;
  if (colon == std::string::npos) return true;
  const char* s = a.c_str() + colon + 1;
  char* end = nullptr;
  errno = 0;
  long p = strtol(s, &end, 10);
  return end != s && *end == '\0' && errno != ERANGE && p >= 1 &&
         p <= 65535;
}

class Coordinator {
 public:
  Coordinator(int size, int port, int64_t fusion_threshold, double stall_secs,
              const std::string& timeline_path)
      : size_(size), port_(port), fusion_threshold_(fusion_threshold),
        stall_secs_(stall_secs) {
    // Batch-window width (the reference's 5 ms background-tick period,
    // mpi_ops.cc:1295); tunable for latency-sensitive eager workloads.
    tick_ms_ = static_cast<int>(ParseEnvI64("HOROVOD_COORD_TICK_MS", 5));
    if (tick_ms_ < 0) tick_ms_ = 0;
    // Liveness deadline (seconds; 0 disables). A rank whose last frame —
    // heartbeat or otherwise — is older than this is declared dead and the
    // world ABORTS. The Elastic-Horovod-era fix for the reference's
    // warn-only stall handling (mpi_ops.cc:1153-1196).
    heartbeat_timeout_ = ParseEnvF64("HVD_HEARTBEAT_TIMEOUT", 30.0);
    if (heartbeat_timeout_ < 0) heartbeat_timeout_ = 0;
    // Resize generation: how many live resizes this job has been through
    // (exported to re-formed/new ranks as HVD_RESIZE_GENERATION so the
    // re-initialized coordinator numbers the NEXT resize correctly and
    // sync-collective names never collide across resizes).
    resize_generation_ =
        static_cast<int32_t>(ParseEnvI64("HVD_RESIZE_GENERATION", 0));
    if (resize_generation_ < 0) resize_generation_ = 0;
    if (!timeline_path.empty()) timeline_.Open(timeline_path);
    listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    int one = 1;
    setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = htonl(INADDR_ANY);
    addr.sin_port = htons(static_cast<uint16_t>(port_));
    if (bind(listen_fd_, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 ||
        listen(listen_fd_, size_) != 0) {
      perror("hvdcoord: coordinator bind/listen");
      ok_ = false;
      return;
    }
    thread_ = std::thread(&Coordinator::Serve, this);
  }

  ~Coordinator() {
    // Live-resize handoff: when the world tears this plane down to
    // re-form (rank 0 calls hvdcoord_shutdown mid-resize), an accepted
    // resize the supervising launcher has NOT yet fetched would vanish
    // with us — and with it the launcher's only way to learn the new
    // port / spawn grow ranks. Hold the teardown briefly (bounded; the
    // launcher polls ~2x/second) until one admin query has seen the
    // pending triple. Skipped when the serve thread already exited
    // (abort path) or nothing is pending.
    if (resize_fetch_pending_.load() && !serve_done_.load()) {
      double linger = ParseEnvF64("HVD_RESIZE_LINGER", 2.0);
      auto deadline = std::chrono::steady_clock::now() +
                      std::chrono::duration<double>(linger < 0 ? 0 : linger);
      while (std::chrono::steady_clock::now() < deadline &&
             resize_fetch_pending_.load() && !serve_done_.load())
        std::this_thread::sleep_for(std::chrono::milliseconds(20));
    }
    shutdown_.store(true);
    if (listen_fd_ >= 0) ::close(listen_fd_);
    if (thread_.joinable()) thread_.join();
    for (int fd : client_fds_)
      if (fd >= 0) ::close(fd);
  }

  bool ok() const { return ok_; }

  // Deterministic-fault-injection hook (HVD_FAULT_SPEC coord:mute@step=N):
  // stop acking client heartbeats so every client observes a silent
  // coordinator and fails over — the only way to exercise the
  // dead-coordinator detection path without a real network partition.
  void set_mute_acks(bool m) { mute_acks_.store(m); }

 private:
  void Serve() {
    // Accept exactly `size` clients; client's first frame is its hello
    // {rank, size, protocol version}. Cross-rank config skew (wrong world
    // size, mismatched build) and malformed/duplicate hellos are rejected
    // with a named error WITHOUT killing the accept loop — a stray
    // connection must not take down the whole world's coordinator
    // (membership-fault hardening; the reference's MPI world membership is
    // fixed by mpirun so it never faces this, but it does validate
    // cross-rank consistency per tensor, mpi_ops.cc:439-449 — here the
    // world-level part happens once, at init).
    client_fds_.assign(size_, -1);
    int accepted = 0;
    while (accepted < size_ && !shutdown_.load()) {
      // Poll-before-accept: a blocked accept() is not reliably woken by
      // closing the listen fd, so a world torn down DURING formation
      // (e.g. its ranks aborted before all peers connected) must not
      // wedge the destructor's thread join forever.
      pollfd lp{listen_fd_, POLLIN, 0};
      int pn = ::poll(&lp, 1, 100);
      if (pn < 0 || (lp.revents & (POLLERR | POLLNVAL | POLLHUP))) {
        serve_done_.store(true);
        return;
      }
      if (pn == 0) continue;  // timeout: re-check shutdown_
      int fd = ::accept(listen_fd_, nullptr, nullptr);
      if (fd < 0) {  // listen socket closed (shutdown path)
        serve_done_.store(true);
        return;
      }
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      // Bound the hello read: a connection that opens and sends nothing (a
      // port scanner, a load-balancer health probe) must not block the
      // accept loop and lock real ranks out of the world.
      timeval hello_timeout{/*tv_sec=*/5, /*tv_usec=*/0};
      setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &hello_timeout,
                 sizeof(hello_timeout));
      std::string hello;
      std::string reject;
      std::string advertise;
      int32_t rank = -1;
      int32_t peer_port = 0;
      bool got = RecvFrame(fd, &hello);
      if (got && hello.size() == 12) {
        // Pre-v4 builds sent a 12-byte {rank, size, version} hello: read
        // far enough to emit the SPECIFIC version-mismatch diagnostic
        // instead of the generic malformed-frame one.
        int32_t cver;
        memcpy(&rank, hello.data(), 4);
        memcpy(&cver, hello.data() + 8, 4);
        std::ostringstream o;
        o << "protocol version mismatch: coordinator speaks v"
          << kProtocolVersion << ", rank " << rank << " speaks v" << cver
          << " (pre-v4 build; mixed horovod_tpu builds in one world)";
        reject = o.str();
      } else if (!got || hello.size() < 16) {
        reject = "malformed hello frame (client/coordinator build mismatch?)";
      } else {
        int32_t csize, cver;
        memcpy(&rank, hello.data(), 4);
        memcpy(&csize, hello.data() + 4, 4);
        memcpy(&cver, hello.data() + 8, 4);
        memcpy(&peer_port, hello.data() + 12, 4);
        // Optional suffix: the rank's advertised ring data-plane address
        // (HOROVOD_RING_ADVERTISE_ADDR) for NAT/multi-homed hosts where
        // the getpeername() source IP is not reachable by ring neighbors.
        if (hello.size() > 16) advertise = hello.substr(16);
        std::ostringstream o;
        if (cver != kProtocolVersion) {
          o << "protocol version mismatch: coordinator speaks v"
            << kProtocolVersion << ", rank " << rank << " speaks v" << cver
            << " (mixed horovod_tpu builds in one world)";
          reject = o.str();
        } else if (csize != size_) {
          o << "world size mismatch: coordinator was launched with size "
            << size_ << ", but rank " << rank << " was launched with size "
            << csize << " (check HVD_SIZE / launcher -np on every host)";
          reject = o.str();
        } else if (rank < 0 || rank >= size_) {
          o << "out-of-range rank " << rank << " for world size " << size_;
          reject = o.str();
        } else if (client_fds_[rank] != -1) {
          o << "duplicate rank " << rank
            << " (two processes claim the same rank; check HVD_RANK)";
          reject = o.str();
        } else if (!advertise.empty() && !ValidAdvertiseAddr(advertise)) {
          o << "malformed ring advertise address \"" << advertise
            << "\" from rank " << rank << " (expected an IPv4 literal "
            << "\"a.b.c.d\" or \"a.b.c.d:port\" with port 1-65535; "
            << "check HOROVOD_RING_ADVERTISE_ADDR on that host)";
          reject = o.str();
        }
      }
      Buf ack;
      ack.PutU8(static_cast<uint8_t>(MsgTag::kHelloAck));
      ack.PutU8(reject.empty() ? 1 : 0);
      ack.PutStr(reject);
      SendFrame(fd, send_mu_, ack.str());
      if (!reject.empty()) {
        fprintf(stderr, "hvdcoord: rejecting client: %s\n", reject.c_str());
        ::close(fd);
        continue;
      }
      // Admitted: back to blocking reads (the tick loop polls first).
      timeval no_timeout{0, 0};
      setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &no_timeout,
                 sizeof(no_timeout));
      client_fds_[rank] = fd;
      // Record the rank's ring data-plane address: its advertised address
      // if it announced one (NAT / multi-homed hosts), else the IP this
      // connection came from + the peer-listen port from the hello.
      {
        if (peer_addrs_.empty()) peer_addrs_.assign(size_, std::string());
        std::ostringstream a;
        if (!advertise.empty()) {
          if (advertise.find(':') != std::string::npos)
            a << advertise;  // full "ip:port" override
          else
            a << advertise << ":" << peer_port;
        } else {
          sockaddr_in peer{};
          socklen_t plen = sizeof(peer);
          char ip[INET_ADDRSTRLEN] = "127.0.0.1";
          if (getpeername(fd, reinterpret_cast<sockaddr*>(&peer), &plen) ==
              0)
            inet_ntop(AF_INET, &peer.sin_addr, ip, sizeof(ip));
          a << ip << ":" << peer_port;
        }
        peer_addrs_[rank] = a.str();
      }
      accepted++;
    }

    // Tick loop. The reference's background thread ticks every 5 ms
    // (mpi_ops.cc:1293-1295): every message that arrived within a tick is
    // drained BEFORE responses are planned, which is what lets concurrent
    // announcements (the async API's in-flight batch) fuse. Mirror that
    // with a batch window: on first arrival, keep ingesting until the
    // window expires and the sockets are drained, then plan responses.
    // This bounds per-collective latency at ~tick_ms (the reference's
    // negotiation latency floor) while letting in-flight batches coalesce.
    // One extra poll slot for the listen socket: it stays open after
    // world formation so ADMIN connections (live-resize requests / status
    // queries, MsgTag::kResizeRequest) can reach a running job. Stray
    // connections cost one bounded read and a close — they cannot wedge
    // or kill the world's coordinator.
    std::vector<pollfd> pfds(size_ + 1);
    int done_ranks = 0;
    // Liveness bookkeeping starts once the world is fully formed: any
    // frame (request, shutdown, heartbeat) from a rank refreshes its
    // last_seen; a rank silent past HVD_HEARTBEAT_TIMEOUT aborts the
    // world. done_[] marks ranks that sent a clean kShutdown — their
    // subsequent disconnect is benign, anyone else's is a worker failure.
    last_seen_.assign(size_, std::chrono::steady_clock::now());
    done_.assign(size_, false);
    while (!shutdown_.load()) {
      for (int i = 0; i < size_; i++)
        pfds[i] = {client_fds_[i], POLLIN, 0};
      pfds[size_] = {listen_fd_, POLLIN, 0};
      int n = ::poll(pfds.data(), pfds.size(), /*ms=*/5);
      if (n < 0) break;
      if (n > 0 && (pfds[size_].revents & POLLIN)) {
        HandleAdminConnection();
        n--;
      }
      if (n > 0) {
        // Quiescence batching: keep ingesting while frames keep arriving
        // within a short grace interval, capped at tick_ms total. A burst
        // of async submits (frames µs–ms apart) coalesces into one fusion
        // pass; a lone synchronous collective pays only the grace (~1 ms),
        // not the full tick — better than the reference's unconditional
        // 5 ms floor (mpi_ops.cc:1295).
        int grace_ms = tick_ms_ > 5 ? tick_ms_ / 5 : 1;
        auto deadline = std::chrono::steady_clock::now() +
                        std::chrono::milliseconds(tick_ms_);
        while (n > 0 && !shutdown_.load()) {
          for (int i = 0; i < size_; i++) {
            if (!(pfds[i].revents & POLLIN)) continue;
            std::string body;
            if (!RecvFrame(client_fds_[i], &body)) {
              if (done_[i]) {
                // Clean-shutdown rank closing its socket: benign. Forget
                // the fd so poll stops watching it.
                ::close(client_fds_[i]);
                client_fds_[i] = -1;
                continue;
              }
              // A rank died mid-run (process killed -> kernel closed its
              // socket). The reference's analog hangs every other rank
              // inside MPI forever; here the world fails fast with the
              // dead rank's identity.
              BroadcastAbort(i, "disconnected without a clean shutdown "
                                "(process crashed or was killed?)");
              serve_done_.store(true);
              return;
            }
            last_seen_[i] = std::chrono::steady_clock::now();
            Reader rd(body);
            MsgTag tag = static_cast<MsgTag>(rd.GetU8());
            if (tag == MsgTag::kHeartbeat) {
              if (!mute_acks_.load()) {
                Buf ack;
                ack.PutU8(static_cast<uint8_t>(MsgTag::kHeartbeatAck));
                // v7: every ack carries the pending-resize triple (0,0,gen
                // when none) — ranks learn of a pending resize on the
                // liveness plane they already pay for, with zero extra
                // collectives on the training hot path.
                ack.PutI32(pending_resize_target_);
                ack.PutI32(pending_resize_port_);
                ack.PutI32(resize_generation_ +
                           (pending_resize_target_ ? 1 : 0));
                SendFrame(client_fds_[i], send_mu_, ack.str());
              }
              continue;
            }
            if (tag == MsgTag::kShutdown) {
              done_[i] = true;
              if (++done_ranks == size_) {
                BroadcastShutdown();
                ResizeHandoffLinger();
                serve_done_.store(true);
                return;
              }
              continue;
            }
            Request req = DecodeRequest(rd);
            Ingest(std::move(req));
          }
          for (int i = 0; i < size_; i++)
            pfds[i] = {client_fds_[i], POLLIN, 0};
          pfds[size_] = {listen_fd_, POLLIN, 0};
          auto left = std::chrono::duration_cast<std::chrono::milliseconds>(
                          deadline - std::chrono::steady_clock::now())
                          .count();
          int wait = left > 0 ? static_cast<int>(
                                    std::min<int64_t>(left, grace_ms))
                              : 0;
          n = ::poll(pfds.data(), pfds.size(), wait);
          if (n < 0) break;
          if (n > 0 && (pfds[size_].revents & POLLIN)) {
            // Admin connection arriving mid-batch: consume it here or the
            // re-poll would spin on it until the tick deadline.
            HandleAdminConnection();
            n--;
          }
        }
      }
      DrainReady();
      CheckStalls();
      if (CheckHeartbeats()) {
        serve_done_.store(true);
        return;
      }
    }
    serve_done_.store(true);
  }

  // Clean-shutdown tail of a live resize: the world's ranks all tore
  // down to re-form, but the supervising launcher may not have fetched
  // the pending triple yet (its admin poll runs ~2x/second; a fast
  // quiesce can beat it). Keep answering admin connections briefly so
  // the handoff cannot be lost — without this, a grow's new ranks would
  // never be spawned. Bounded hard at 10 s so an unsupervised job still
  // exits.
  void ResizeHandoffLinger() {
    if (!resize_fetch_pending_.load()) return;
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::seconds(10);
    while (!shutdown_.load() && resize_fetch_pending_.load() &&
           std::chrono::steady_clock::now() < deadline) {
      pollfd lp{listen_fd_, POLLIN, 0};
      int pn = ::poll(&lp, 1, 50);
      if (pn < 0 || (lp.revents & (POLLERR | POLLNVAL | POLLHUP)))
        return;
      if (pn > 0 && (lp.revents & POLLIN)) HandleAdminConnection();
    }
  }

  // IncrementTensorCount semantics (mpi_ops.cc:233-258).
  void Ingest(Request req) {
    auto& p = table_[req.name];
    if (p.requests.empty()) {
      p.announced.assign(size_, false);
      p.first_seen = std::chrono::steady_clock::now();
      arrival_order_.push_back(req.name);
      if (timeline_.enabled()) {
        // Phase 1 "NEGOTIATE_<OP>" (timeline.cc:107-140 naming).
        timeline_.NegotiateStart(req.name, ReqTypeName(req.type));
      }
    }
    if (timeline_.enabled()) {
      timeline_.NegotiateRankReady(req.name, req.rank);
    }
    if (!p.announced[req.rank]) {
      p.announced[req.rank] = true;
      p.count++;
      p.requests.push_back(std::move(req));
    }
    // Duplicate announcement from the same rank for an in-flight name is
    // dropped (Python auto-naming makes names unique per call).
  }

  // Process fully-announced tensors in strict arrival order, fusing
  // consecutive same-dtype allreduce responses within the threshold into one
  // frame — the reference's coordinator-side tensor fusion
  // (mpi_ops.cc:1395-1422: same response type, same dtype, size-capped,
  // stop at the first non-fusable response so request order is preserved).
  // The compiled data plane has its own fusion (ops/fusion.py gradient
  // bucketing); this is the host eager plane's, fed by the async API's
  // in-flight concurrency (reference: ComputeAsync kernels,
  // mpi_ops.cc:1752-1772).
  // A fully-announced allgather may mix ALLGATHER (payload shipped) and
  // ALLGATHER_RING (payload held back) when per-rank block sizes straddle
  // HOROVOD_RING_THRESHOLD — a legitimate ragged input, not config skew.
  // Resolve by demoting: tell the ring announcers to resubmit as star,
  // un-count them, and keep the op pending until the payloads arrive.
  bool DemoteMixedGatherRing(const std::string& name, PendingTensor* p) {
    bool star = false, ring = false;
    for (auto& r : p->requests) {
      star = star || r.type == ReqType::kAllgather;
      ring = ring || r.type == ReqType::kAllgatherRing;
    }
    if (!star || !ring) return false;
    Response resp;
    resp.type = RespType::kResubmitStar;
    resp.name = name;
    std::string body = EncodeResponse(resp);
    for (auto it = p->requests.begin(); it != p->requests.end();) {
      if (it->type == ReqType::kAllgatherRing) {
        SendFrame(client_fds_[it->rank], send_mu_, body);
        p->announced[it->rank] = false;
        p->count--;
        it = p->requests.erase(it);
      } else {
        ++it;
      }
    }
    return true;
  }

  void DrainReady() {
    std::vector<std::string> ready;
    for (auto it = arrival_order_.begin(); it != arrival_order_.end();) {
      auto t = table_.find(*it);
      if (t != table_.end() && t->second.count == size_) {
        if (DemoteMixedGatherRing(*it, &t->second)) {
          ++it;  // stays pending until the star resubmissions land
          continue;
        }
        ready.push_back(*it);
        it = arrival_order_.erase(it);
      } else {
        ++it;
      }
    }
    std::vector<Response> resps;
    resps.reserve(ready.size());
    for (auto& name : ready) resps.push_back(BuildResponse(name));

    size_t i = 0;
    while (i < resps.size()) {
      if (!Fusable(resps[i]) || fusion_threshold_ <= 0) {
        Emit(resps[i]);
        i++;
        continue;
      }
      // Extend the fusion group while the next response is fusable with the
      // head (same dtype, cumulative bytes under the threshold).
      size_t j = i + 1;
      int64_t total = static_cast<int64_t>(resps[i].payload.size());
      while (j < resps.size() && Fusable(resps[j]) &&
             resps[j].dtype == resps[i].dtype &&
             total + static_cast<int64_t>(resps[j].payload.size()) <=
                 fusion_threshold_) {
        total += static_cast<int64_t>(resps[j].payload.size());
        j++;
      }
      if (j - i == 1) {
        Emit(resps[i]);
      } else {
        EmitFused(resps, i, j);
      }
      i = j;
    }
  }

  static bool Fusable(const Response& r) {
    return r.type == RespType::kAllreduce && r.per_rank_payloads.empty() &&
           !r.payload.empty();
  }

  // ConstructMPIResponse parity (mpi_ops.cc:266-474): cross-rank validation
  // with the reference's error classification, then host execution.
  Response BuildResponse(const std::string& name) {
    auto it = table_.find(name);
    auto requests = std::move(it->second.requests);
    table_.erase(it);

    Response resp;
    resp.name = name;
    std::ostringstream err;

    if (timeline_.enabled()) {
      // Close phase 1 with the first-arrived request's op (the name the
      // NEGOTIATE_* begin event used); the top-level processing event opens
      // below once validation passes (timeline.cc:142-166 Start).
      timeline_.NegotiateEnd(name, ReqTypeName(requests.front().type));
    }

    // Order requests by rank for deterministic gather concat.
    std::sort(requests.begin(), requests.end(),
              [](const Request& a, const Request& b) { return a.rank < b.rank; });

    // Unknown type bytes (a direct/nonconforming client; conforming mixed
    // builds are already rejected at hello by the version check) must
    // produce a named error, never reach the op switches below.
    for (auto& r : requests) {
      if (!KnownReqType(r.type)) {
        err << "Unknown collective operation type "
            << static_cast<int>(r.type) << " announced by rank " << r.rank
            << " (nonconforming client).";
        resp.type = RespType::kError;
        resp.error = err.str();
        return resp;
      }
    }

    DType dtype = requests[0].dtype;
    resp.dtype = dtype;
    for (auto& r : requests) {
      if (r.dtype != dtype) {
        err << "Mismatched data types: One rank had type " << DTypeName(dtype)
            << ", but another rank had type " << DTypeName(r.dtype) << ".";
        resp.type = RespType::kError;
        resp.error = err.str();
        return resp;
      }
    }
    // Broadcast family: only the ROOT ships payload, so only its
    // election decides star vs ring; non-roots always announce plain
    // BROADCAST. Normalize before the mismatch check (a ring
    // announcement from a NON-root is left un-normalized and caught as
    // a genuine mismatch below).
    bool bcast_ring = false;
    {
      bool family = true;
      for (auto& r : requests)
        family = family && (r.type == ReqType::kBroadcast ||
                            r.type == ReqType::kBroadcastRing);
      if (family) {
        bool roots_only = true;
        bool any_ring = false;
        for (auto& r : requests)
          if (r.type == ReqType::kBroadcastRing) {
            any_ring = true;
            roots_only = roots_only && r.rank == r.root_rank;
          }
        if (any_ring && roots_only) {
          bcast_ring = true;
          for (auto& r : requests) r.type = ReqType::kBroadcast;
        }
      }
    }

    ReqType op = requests[0].type;
    for (auto& r : requests) {
      if (r.type != op) {
        err << "Mismatched collective operations: One rank did an "
            << ReqTypeName(op) << ", but another rank did an "
            << ReqTypeName(r.type) << ".";
        resp.type = RespType::kError;
        resp.error = err.str();
        return resp;
      }
    }

    // A kBroadcastRing that survived normalization means every announcer
    // sent it from a NON-root rank (only possible with a nonconforming or
    // direct client — conforming non-roots always announce plain
    // BROADCAST). It must not skip root validation below.
    if (op == ReqType::kBroadcastRing) {
      err << "BROADCAST_RING announced by a non-root rank (only the "
          << "broadcast root elects the ring plane; nonconforming client).";
      resp.type = RespType::kError;
      resp.error = err.str();
      return resp;
    }

    if (op == ReqType::kAllreduce || op == ReqType::kReducescatter ||
        op == ReqType::kAllreduceRing ||
        op == ReqType::kReducescatterRing) {
      RedOp rop = requests[0].red_op;
      for (auto& r : requests) {
        if (r.red_op != rop) {
          err << "Mismatched reduction ops: One rank requested "
              << RedOpName(rop) << ", but another rank requested "
              << RedOpName(r.red_op) << ".";
          resp.type = RespType::kError;
          resp.error = err.str();
          return resp;
        }
      }
    }

    if (op == ReqType::kAllreduce || op == ReqType::kBroadcast ||
        op == ReqType::kAlltoall || op == ReqType::kReducescatter ||
        op == ReqType::kAllreduceRing || op == ReqType::kAlltoallRing ||
        op == ReqType::kReducescatterRing) {
      const auto& shape = requests[0].shape;
      for (auto& r : requests) {
        if (r.shape != shape) {
          err << "Mismatched " << ReqTypeName(op)
              << " tensor shapes: One rank sent a tensor of shape "
              << ShapeStr(shape)
              << ", but another rank sent a tensor of shape "
              << ShapeStr(r.shape) << ".";
          resp.type = RespType::kError;
          resp.error = err.str();
          return resp;
        }
      }
    }

    if (op == ReqType::kAllgather || op == ReqType::kAllgatherRing) {
      const auto& shape0 = requests[0].shape;
      if (shape0.empty()) {
        err << "Rank zero tried to ALLGATHER a rank-zero tensor.";
        resp.type = RespType::kError;
        resp.error = err.str();
        return resp;
      }
      resp.sizes.assign(size_, 0);
      for (auto& r : requests) {
        if (r.shape.size() != shape0.size()) {
          err << "Mismatched ALLGATHER tensor shapes: One rank sent a tensor "
              << "of rank " << shape0.size()
              << ", but another rank sent a tensor of rank "
              << r.shape.size() << ".";
          resp.type = RespType::kError;
          resp.error = err.str();
          return resp;
        }
        for (size_t d = 1; d < shape0.size(); d++) {
          if (r.shape[d] != shape0[d]) {
            err << "Mismatched ALLGATHER tensor shapes: One rank sent a "
                << "tensor with dimension " << d << " equal to " << shape0[d]
                << ", but another rank sent a tensor with dimension " << d
                << " equal to " << r.shape[d] << ".";
            resp.type = RespType::kError;
            resp.error = err.str();
            return resp;
          }
        }
        resp.sizes[r.rank] = r.shape[0];
      }
    }

    if (op == ReqType::kBroadcast) {
      int root = requests[0].root_rank;
      if (root < 0 || root >= size_) {
        // Out-of-range root is rejected here too (the public Python API
        // range-checks, but a direct client call must not index out of
        // bounds; reference root validation: ConstructMPIResponse region
        // mpi_ops.cc:408-435).
        err << "Invalid BROADCAST root rank " << root << ": world size is "
            << size_ << ".";
        resp.type = RespType::kError;
        resp.error = err.str();
        return resp;
      }
      for (auto& r : requests) {
        if (r.root_rank != root) {
          err << "Mismatched BROADCAST root ranks: One rank specified root "
              << "rank " << root << ", but another rank specified root rank "
              << r.root_rank << ".";
          resp.type = RespType::kError;
          resp.error = err.str();
          return resp;
        }
      }
    }

    // Payload byte counts must match the announced shapes: the host
    // executors trust the shapes (the striped reduce indexes every rank's
    // payload by the ACCUMULATOR's extent; concat trusts per-rank dim-0),
    // so a nonconforming client shipping a short payload would otherwise
    // cause an out-of-bounds read that can kill the coordinator — the
    // same threat class as the unknown-type and non-root-ring checks.
    // Conforming clients always match; ring announcements ship no bytes.
    if (op == ReqType::kAllreduce || op == ReqType::kAllgather ||
        op == ReqType::kAlltoall || op == ReqType::kReducescatter ||
        op == ReqType::kBroadcast) {
      for (auto& r : requests) {
        int64_t elems = 1;
        for (int64_t d : r.shape) elems *= d;
        size_t want = static_cast<size_t>(elems) *
                      static_cast<size_t>(DTypeSize(r.dtype));
        if (op == ReqType::kBroadcast) {
          // Only the root ships payload; a ring-elected root stashed its
          // bytes client-side, so its announcement is empty too.
          want = (r.rank == requests[0].root_rank && !bcast_ring) ? want : 0;
        }
        if (r.payload.size() != want) {
          err << "Mismatched payload size: rank " << r.rank
              << " announced shape " << ShapeStr(r.shape) << " ("
              << want << " bytes of " << DTypeName(r.dtype)
              << ") but shipped " << r.payload.size()
              << " bytes (nonconforming client).";
          resp.type = RespType::kError;
          resp.error = err.str();
          return resp;
        }
      }
    }

    if (op == ReqType::kAlltoall || op == ReqType::kReducescatter ||
        op == ReqType::kAlltoallRing || op == ReqType::kReducescatterRing) {
      const auto& shape0 = requests[0].shape;
      if (shape0.empty() || shape0[0] % size_ != 0) {
        err << ReqTypeName(op) << " requires a first dimension divisible by "
            << "the world size " << size_ << ", got shape "
            << ShapeStr(shape0) << ".";
        resp.type = RespType::kError;
        resp.error = err.str();
        return resp;
      }
    }

    // Execute the host data plane. The top-level processing event wraps a
    // named activity per op (reference nested activities,
    // mpi_ops.cc:623-635 / docs/timeline.md:25-43; MPI_ALLREDUCE et al.
    // become host-plane SUM/CONCAT/BCAST/ALLTOALL/REDUCESCATTER).
    const char* act = nullptr;
    switch (op) {
      case ReqType::kAllreduce: act = "SUM"; break;
      case ReqType::kAllgather: act = "CONCAT"; break;
      case ReqType::kBroadcast: act = "BCAST"; break;
      case ReqType::kAlltoall: act = "ALLTOALL"; break;
      case ReqType::kReducescatter: act = "REDUCESCATTER"; break;
      case ReqType::kAllreduceRing: act = "RING_PLAN"; break;
      case ReqType::kAllgatherRing: act = "RING_PLAN"; break;
      case ReqType::kBroadcastRing: act = "RING_PLAN"; break;
      case ReqType::kAlltoallRing: act = "RING_PLAN"; break;
      case ReqType::kReducescatterRing: act = "RING_PLAN"; break;
    }
    if (timeline_.enabled()) {
      timeline_.Start(resp.name, ReqTypeName(op));  // top-level Start
      timeline_.ActivityStart(resp.name, act);
    }
    switch (op) {
      case ReqType::kAllreduce: {
        resp.type = RespType::kAllreduce;
        resp.shape = requests[0].shape;
        resp.payload = requests[0].payload;
        ReduceAllStriped(dtype, requests[0].red_op, &resp.payload, requests);
        break;
      }
      case ReqType::kAllgather: {
        resp.type = RespType::kAllgather;
        resp.shape = requests[0].shape;
        resp.shape[0] = 0;
        for (auto& r : requests) {
          resp.payload += r.payload;  // rank order
          resp.shape[0] += r.shape[0];
        }
        break;
      }
      case ReqType::kBroadcast: {
        if (bcast_ring) {
          // Chain plan: no payload through the coordinator; sizes[0]
          // carries the root for the clients' chain orientation.
          resp.type = RespType::kBroadcastRing;
          resp.shape = requests[0].shape;
          resp.sizes = {requests[0].root_rank};
          resp.ring_peers = peer_addrs_;
          break;
        }
        resp.type = RespType::kBroadcast;
        resp.shape = requests[0].shape;
        resp.payload = requests[requests[0].root_rank].payload;
        break;
      }
      case ReqType::kAlltoall: {
        // Rank r's result = concat over senders s of block r of s's tensor
        // (lax.all_to_all split_axis=0, concat_axis=0 semantics).
        resp.type = RespType::kAlltoall;
        resp.shape = requests[0].shape;
        size_t block = requests[0].payload.size() / size_;
        resp.per_rank_payloads.assign(size_, std::string());
        for (int r = 0; r < size_; r++) {
          resp.per_rank_payloads[r].reserve(block * size_);
          for (int s = 0; s < size_; s++)
            resp.per_rank_payloads[r] +=
                requests[s].payload.substr(r * block, block);
        }
        break;
      }
      case ReqType::kAllreduceRing: {
        // No host execution: ship the ring plan; clients move the data
        // among themselves (reduce-scatter + allgather over the rank ring).
        resp.type = RespType::kAllreduceRing;
        resp.shape = requests[0].shape;
        resp.ring_peers = peer_addrs_;
        break;
      }
      case ReqType::kAllgatherRing: {
        // resp.sizes (per-rank first dims) was filled by the allgather
        // validation above; clients circulate their blocks themselves.
        resp.type = RespType::kAllgatherRing;
        resp.shape = requests[0].shape;
        resp.ring_peers = peer_addrs_;
        break;
      }
      case ReqType::kAlltoallRing: {
        // Mesh plan: clients exchange blocks pairwise among themselves.
        resp.type = RespType::kAlltoallRing;
        resp.shape = requests[0].shape;
        resp.ring_peers = peer_addrs_;
        break;
      }
      case ReqType::kReducescatterRing: {
        // Ring plan: clients run the reduce-scatter phase themselves.
        resp.type = RespType::kReducescatterRing;
        resp.shape = requests[0].shape;
        resp.ring_peers = peer_addrs_;
        break;
      }
      case ReqType::kBroadcastRing:
        break;  // unreachable: rejected above (non-root BROADCAST_RING)
      case ReqType::kReducescatter: {
        // Sum all tensors, rank r receives block r of the first dimension
        // (lax.psum_scatter tiled semantics).
        resp.type = RespType::kReducescatter;
        resp.shape = requests[0].shape;
        resp.shape[0] /= size_;
        std::string sum = requests[0].payload;
        ReduceAllStriped(dtype, requests[0].red_op, &sum, requests);
        size_t block = sum.size() / size_;
        resp.per_rank_payloads.assign(size_, std::string());
        for (int r = 0; r < size_; r++)
          resp.per_rank_payloads[r] = sum.substr(r * block, block);
        break;
      }
    }
    if (timeline_.enabled()) timeline_.ActivityEnd(resp.name, act);
    return resp;
  }

  // End-event args: output dtype + shape (timeline.cc:203-220 parity).
  static std::string TimelineArgs(const Response& r) {
    std::ostringstream o;
    o << "{\"dtype\":\"" << DTypeName(r.dtype) << "\",\"shape\":"
      << ShapeStr(r.shape) << "}";
    return o.str();
  }

  void Emit(Response& resp) {
    if (resp.type == RespType::kError) {
      // Validation failed before the top-level event opened; the ERROR
      // send is its own top-level pair.
      if (timeline_.enabled()) timeline_.Start(resp.name, "ERROR");
      std::string body = EncodeResponse(resp);
      for (int r = 0; r < size_; r++)
        SendFrame(client_fds_[r], send_mu_, body);
      if (timeline_.enabled()) timeline_.End(resp.name);
      return;
    }
    if (timeline_.enabled()) timeline_.ActivityStart(resp.name, "RESPOND");
    if (resp.per_rank_payloads.empty()) {
      std::string body = EncodeResponse(resp);
      for (int r = 0; r < size_; r++)
        SendFrame(client_fds_[r], send_mu_, body);
    } else {
      // alltoall/reducescatter: each rank receives its own result slice.
      for (int r = 0; r < size_; r++) {
        resp.payload = resp.per_rank_payloads[r];
        SendFrame(client_fds_[r], send_mu_, EncodeResponse(resp));
      }
    }
    if (timeline_.enabled()) {
      timeline_.ActivityEnd(resp.name, "RESPOND");
      timeline_.End(resp.name, TimelineArgs(resp));  // top-level
    }
  }

  // Fused emission: one frame answering resps[lo, hi) at once
  // (mpi_ops.cc:1395-1422 response batching; tensor_names[] >1 ⇒ fused).
  void EmitFused(std::vector<Response>& resps, size_t lo, size_t hi) {
    Response out;
    out.type = RespType::kAllreduce;
    out.name = resps[lo].name;
    for (size_t k = lo; k < hi; k++) {
      out.fused_names.push_back(resps[k].name);
      out.fused_nbytes.push_back(
          static_cast<int64_t>(resps[k].payload.size()));
      out.payload += resps[k].payload;
      if (timeline_.enabled())
        timeline_.ActivityStart(resps[k].name, "RESPOND");
    }
    std::string body = EncodeResponse(out);
    for (int r = 0; r < size_; r++) SendFrame(client_fds_[r], send_mu_, body);
    if (timeline_.enabled()) {
      for (size_t k = lo; k < hi; k++) {
        timeline_.ActivityEnd(resps[k].name, "RESPOND");
        timeline_.End(resps[k].name, TimelineArgs(resps[k]));
      }
    }
  }

  void BroadcastShutdown() {
    Response resp;
    resp.type = RespType::kShutdown;
    resp.name = "__shutdown__";
    std::string body = EncodeResponse(resp);
    for (int r = 0; r < size_; r++)
      if (client_fds_[r] >= 0) SendFrame(client_fds_[r], send_mu_, body);
  }

  // Declare the world dead because of `dead_rank`: every surviving rank's
  // blocked hvdcoord_wait fails fast with the dead rank's identity
  // (-> WorkerFailureError) instead of hanging on collectives that can
  // never complete. Sent to the dead rank too when its socket is still up
  // (alive-but-silent ranks deserve the diagnosis as much as survivors).
  void BroadcastAbort(int dead_rank, const std::string& why) {
    Response resp;
    resp.type = RespType::kAbort;
    resp.name = "__abort__";
    std::ostringstream o;
    o << "worker failure: rank " << dead_rank << " " << why
      << "; aborting the world — in-flight and future collectives on "
      << "every rank fail with this error";
    resp.error = o.str();
    fprintf(stderr, "hvdcoord: %s\n", resp.error.c_str());
    std::string body = EncodeResponse(resp);
    for (int r = 0; r < size_; r++)
      if (client_fds_[r] >= 0) SendFrame(client_fds_[r], send_mu_, body);
  }

  // -- admin plane (v7): live-resize ingress -------------------------------
  // One bounded request/reply exchange per connection, handled inline on
  // the serve thread: accept, read ONE frame under a short timeout, reply,
  // close. A resize request records the pending target and pushes a
  // kResizeNotice to every rank; ranks quiesce at their next step boundary
  // (horovod_tpu.elastic.ResizeCoordinator) — the coordinator itself never
  // interrupts in-flight collectives.

  // Reserve a port for the NEW world's coordinator: bind an ephemeral
  // socket, record its port, close it. The standard free-port probe (same
  // race tolerance as the launcher's): the port is handed to every rank in
  // the notice, and the re-formed rank 0 binds it within the connect
  // budget of the others.
  static int32_t ProbeFreePort() {
    int s = ::socket(AF_INET, SOCK_STREAM, 0);
    if (s < 0) return 0;
    sockaddr_in a{};
    a.sin_family = AF_INET;
    a.sin_addr.s_addr = htonl(INADDR_ANY);
    a.sin_port = 0;
    int32_t port = 0;
    socklen_t alen = sizeof(a);
    if (::bind(s, reinterpret_cast<sockaddr*>(&a), sizeof(a)) == 0 &&
        getsockname(s, reinterpret_cast<sockaddr*>(&a), &alen) == 0)
      port = ntohs(a.sin_port);
    ::close(s);
    return port;
  }

  void BroadcastResizeNotice() {
    Response resp;
    resp.type = RespType::kResizeNotice;
    resp.name = "__resize__";
    resp.sizes = {pending_resize_target_, pending_resize_port_,
                  resize_generation_ + 1};
    std::string body = EncodeResponse(resp);
    for (int r = 0; r < size_; r++)
      if (client_fds_[r] >= 0 && !done_.empty() && !done_[r])
        SendFrame(client_fds_[r], send_mu_, body);
  }

  // Admin requests are a few bytes; anything bigger is not ours. The cap
  // keeps a hostile length prefix from allocating kMaxFrameBytes on the
  // training host (RecvFrame's general bound exists for tensor payloads).
  static constexpr uint64_t kMaxAdminFrameBytes = 4096;

  // Bounded-WALL-CLOCK read: SO_RCVTIMEO only bounds each recv, so a
  // drip-feeding client (1 byte/second) could otherwise park the serve
  // thread for minutes and starve heartbeat acks into a world abort.
  static bool RecvAllDeadline(int fd, void* p, size_t n,
                              std::chrono::steady_clock::time_point dl) {
    size_t off = 0;
    while (off < n) {
      if (std::chrono::steady_clock::now() >= dl) return false;
      ssize_t r = ::recv(fd, reinterpret_cast<char*>(p) + off, n - off, 0);
      if (r <= 0) return false;  // EOF, error, or SO_RCVTIMEO tick
      off += static_cast<size_t>(r);
    }
    return true;
  }

  static bool RecvAdminFrame(int fd, std::string* body) {
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::seconds(2);
    uint64_t len;
    if (!RecvAllDeadline(fd, &len, 8, deadline)) return false;
    if (len > kMaxAdminFrameBytes) return false;
    body->resize(len);
    return len == 0 || RecvAllDeadline(fd, &(*body)[0], len, deadline);
  }

  void HandleAdminConnection() {
    int fd = ::accept(listen_fd_, nullptr, nullptr);
    if (fd < 0) return;
    // Handled inline on the serve thread: keep the stall window a
    // connection can inflict small (a held-open probe costs one second,
    // not five — this port shares the hello port's trusted-cluster
    // model, but a stray health checker must not starve heartbeat acks
    // into an HVD_HEARTBEAT_TIMEOUT abort).
    timeval admin_timeout{/*tv_sec=*/1, /*tv_usec=*/0};
    setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &admin_timeout,
               sizeof(admin_timeout));
    setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &admin_timeout,
               sizeof(admin_timeout));
    std::string body;
    bool ok = false;
    bool accepted_now = false;
    bool supervisor_fetch = false;
    std::string msg;
    if (!RecvAdminFrame(fd, &body) || body.size() < 5 ||
        static_cast<MsgTag>(body[0]) != MsgTag::kResizeRequest) {
      // Port scanner / health probe / mixed-build admin: close without
      // reply beyond the error frame; the world is unaffected.
      msg = "malformed admin frame (expected kResizeRequest)";
    } else {
      Reader rd(body);
      rd.GetU8();  // tag
      int32_t target = rd.GetI32();
      // target 0 = anyone's status query; -1 = the SUPERVISING launcher's
      // status poll — only the latter releases the teardown-handoff
      // linger (a third-party operator's query must not consume the
      // launcher's one chance to learn the grow spawns).
      if (target == 0 || target == -1) {
        ok = true;
        supervisor_fetch = target == -1;
      } else if (target < 0) {
        std::ostringstream o;
        o << "invalid resize target " << target;
        msg = o.str();
      } else if (target == 1 && size_ > 1) {
        msg = "resizing a multi-process world to a single rank is not "
              "supported (the coordination plane needs >= 2 ranks); "
              "relaunch with -np 1 instead (the canonical checkpoint "
              "form restores at any world size)";
      } else if (target == size_ && pending_resize_target_ == 0) {
        std::ostringstream o;
        o << "world is already size " << size_ << "; nothing to resize";
        msg = o.str();
      } else if (pending_resize_target_ != 0) {
        if (target == pending_resize_target_) {
          ok = true;  // idempotent re-request of the same resize
        } else {
          std::ostringstream o;
          o << "resize to " << pending_resize_target_
            << " already pending (generation " << resize_generation_ + 1
            << "); the world must quiesce and re-form before another "
            << "resize can be requested";
          msg = o.str();
        }
      } else {
        int32_t port = ProbeFreePort();
        if (port == 0) {
          msg = "could not reserve a coordinator port for the new world";
        } else {
          pending_resize_target_ = target;
          pending_resize_port_ = port;
          ok = true;
          accepted_now = true;
          // The supervising launcher must see this pending resize at
          // least once (its status poll, or a later idempotent
          // re-request) before the old plane may die — see
          // ResizeHandoffLinger.
          resize_fetch_pending_.store(true);
          fprintf(stderr,
                  "hvdcoord: live resize requested: world %d -> %d "
                  "(generation %d, new coordinator port %d); notifying "
                  "ranks — they quiesce at their next step boundary\n",
                  size_, target, resize_generation_ + 1, port);
          BroadcastResizeNotice();
        }
      }
    }
    Buf reply;
    reply.PutU8(static_cast<uint8_t>(MsgTag::kResizeReply));
    reply.PutU8(ok ? 1 : 0);
    reply.PutStr(msg);
    reply.PutI32(size_);
    reply.PutI32(pending_resize_target_);
    reply.PutI32(pending_resize_port_);
    reply.PutI32(resize_generation_ + (pending_resize_target_ ? 1 : 0));
    bool sent = SendFrame(fd, send_mu_, reply.str());
    ::close(fd);
    // Only the SUPERVISOR's status poll (target = -1) releases the
    // teardown linger: it is the party that must learn the triple to
    // spawn grow ranks. Operator queries and the accepting request pass
    // through without consuming the handoff.
    if (sent && ok && pending_resize_target_ && !accepted_now
        && supervisor_fetch)
      resize_fetch_pending_.store(false);
  }

  // Liveness sweep: a rank (not cleanly shut down) whose last frame is
  // older than HVD_HEARTBEAT_TIMEOUT is dead or wedged — abort. Returns
  // true when the world was aborted (the serve loop must exit).
  bool CheckHeartbeats() {
    if (heartbeat_timeout_ <= 0) return false;
    auto now = std::chrono::steady_clock::now();
    for (int i = 0; i < size_; i++) {
      if (done_[i] || client_fds_[i] < 0) continue;
      double silent =
          std::chrono::duration<double>(now - last_seen_[i]).count();
      if (silent > heartbeat_timeout_) {
        std::ostringstream o;
        o << "went silent (no heartbeat for " << silent
          << " s > HVD_HEARTBEAT_TIMEOUT=" << heartbeat_timeout_
          << " s; process wedged or network partitioned?)";
        BroadcastAbort(i, o.str());
        return true;
      }
    }
    return false;
  }

  // CheckForStalledTensors parity (mpi_ops.cc:1153-1196): warn on stderr for
  // tensors waiting > stall_secs with only a subset of ranks ready.
  void CheckStalls() {
    auto now = std::chrono::steady_clock::now();
    if (std::chrono::duration<double>(now - last_stall_check_).count() <
        stall_secs_)
      return;
    last_stall_check_ = now;
    bool preamble = false;
    for (auto& name : arrival_order_) {
      auto it = table_.find(name);
      if (it == table_.end()) continue;
      double waited =
          std::chrono::duration<double>(now - it->second.first_seen).count();
      if (waited > stall_secs_) {
        if (!preamble) {
          fprintf(stderr,
                  "WARNING: One or more tensors were submitted to be reduced, "
                  "gathered or broadcasted by subset of ranks and are waiting "
                  "for remainder of ranks for more than %.0f seconds. This may "
                  "indicate that different ranks are trying to submit "
                  "different tensors or that only subset of ranks is "
                  "submitting tensors, which will cause deadlock.\n",
                  stall_secs_);
          fprintf(stderr, "Stalled ops:");
          preamble = true;
        }
        fprintf(stderr, "\n%s [ready ranks:", name.c_str());
        for (int r = 0; r < size_; r++)
          if (it->second.announced[r]) fprintf(stderr, " %d", r);
        fprintf(stderr, "]");
      }
    }
    if (preamble) fprintf(stderr, "\n");
  }

  static std::string ShapeStr(const std::vector<int64_t>& s) {
    std::ostringstream o;
    o << "[";
    for (size_t i = 0; i < s.size(); i++) o << (i ? "," : "") << s[i];
    o << "]";
    return o.str();
  }

  int size_;
  int port_;
  int64_t fusion_threshold_;
  double stall_secs_;
  int tick_ms_ = 5;
  double heartbeat_timeout_ = 30.0;
  // Pending live resize (admin plane, v7). Written and read on the serve
  // thread only (admin connections are handled inline in the tick loop);
  // the fetch/serve-done flags are additionally read by the destructor
  // (teardown-linger handoff) and are atomic.
  int32_t pending_resize_target_ = 0;  // 0 = none
  int32_t pending_resize_port_ = 0;    // coordinator port for the NEW world
  int32_t resize_generation_ = 0;
  std::atomic<bool> resize_fetch_pending_{false};
  std::atomic<bool> serve_done_{false};
  std::atomic<bool> mute_acks_{false};
  std::vector<std::chrono::steady_clock::time_point> last_seen_;
  std::vector<bool> done_;
  bool ok_ = true;
  int listen_fd_ = -1;
  std::vector<int> client_fds_;
  std::thread thread_;
  std::atomic<bool> shutdown_{false};
  std::mutex send_mu_;
  Timeline timeline_;

  std::unordered_map<std::string, PendingTensor> table_;  // MessageTable
  std::vector<std::string> peer_addrs_;  // rank -> "ip:port" ring data plane
  std::vector<std::string> arrival_order_;
  std::chrono::steady_clock::time_point last_stall_check_ =
      std::chrono::steady_clock::now();
};

// ---------------------------------------------------------------------------
// Client (every rank, incl. 0): sends requests, receiver thread completes ops.
// ---------------------------------------------------------------------------

class Client {
 public:
  Client(int rank, int size, const std::string& host, int port)
      : rank_(rank), size_(size) {
    // Ring data-plane threshold (bytes): collectives at or above it skip
    // the star and move data client-to-client. 0 disables. Must agree
    // across ranks (skew produces a self-explaining ALLREDUCE vs
    // ALLREDUCE_RING mismatch error at negotiation).
    ring_threshold_ = ParseEnvI64("HOROVOD_RING_THRESHOLD", 4 << 20);
    if (ring_threshold_ < 0) ring_threshold_ = 0;
    if (rank_ == 0 && getenv("HOROVOD_RING_THRESHOLD"))
      fprintf(stderr, "hvdcoord: ring threshold resolved to %lld bytes\n",
              static_cast<long long>(ring_threshold_));
    // Strict stall mode: Wait() fails with a StalledError after this many
    // seconds (0 = off; the reference only warns, mpi_ops.cc:1153-1196).
    stall_timeout_secs_ = ParseEnvF64("HOROVOD_STALL_TIMEOUT", 0.0);
    if (stall_timeout_secs_ < 0) stall_timeout_secs_ = 0;
    // Ring data-plane IO bound (seconds): peer connect/accept and every
    // per-chunk send/recv must finish within it, so a rank dying mid-ring
    // degrades to a TransportError on the survivors instead of an
    // unbounded block on a silent socket.
    ring_io_secs_ =
        static_cast<int>(ParseEnvI64("HOROVOD_RING_IO_TIMEOUT", 30));
    if (ring_io_secs_ < 1) ring_io_secs_ = 1;
    // Liveness deadline, symmetric with the coordinator's: this client
    // beats every ~timeout/4 and expects acks; no ack for a full timeout
    // means the coordinator is dead or wedged -> abort locally (0 = off).
    heartbeat_timeout_ = ParseEnvF64("HVD_HEARTBEAT_TIMEOUT", 30.0);
    if (heartbeat_timeout_ < 0) heartbeat_timeout_ = 0;
    peer_fds_.assign(size_, -1);
    // Peer-listen socket for the ring data plane (ephemeral port, announced
    // in the hello; the left ring neighbor connects here).
    peer_listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    if (peer_listen_fd_ >= 0) {
      int pone = 1;
      setsockopt(peer_listen_fd_, SOL_SOCKET, SO_REUSEADDR, &pone,
                 sizeof(pone));
      sockaddr_in paddr{};
      paddr.sin_family = AF_INET;
      paddr.sin_addr.s_addr = htonl(INADDR_ANY);
      paddr.sin_port = 0;
      if (bind(peer_listen_fd_, reinterpret_cast<sockaddr*>(&paddr),
               sizeof(paddr)) == 0 &&
          listen(peer_listen_fd_, size) == 0) {  // mesh: several peers connect at once
        socklen_t alen = sizeof(paddr);
        if (getsockname(peer_listen_fd_,
                        reinterpret_cast<sockaddr*>(&paddr), &alen) == 0)
          peer_port_ = ntohs(paddr.sin_port);
      }
      if (peer_port_ == 0) {
        ::close(peer_listen_fd_);
        peer_listen_fd_ = -1;
      }
    }
    fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons(static_cast<uint16_t>(port));
    inet_pton(AF_INET, host.c_str(), &addr.sin_addr);
    // Retry connect under a wall-clock budget with bounded exponential
    // backoff: the coordinator may not be up yet (launcher spawns ranks
    // concurrently; a restarted world reopens on a fresh port). The old
    // fixed 50 ms x 600 schedule hammered the host during long restarts
    // and gave no knob for slow multi-host bring-up.
    double connect_budget = ParseEnvF64("HVD_COORD_CONNECT_TIMEOUT", 30.0);
    if (connect_budget < 0) connect_budget = 0;
    auto cdeadline = std::chrono::steady_clock::now() +
                     std::chrono::duration<double>(connect_budget);
    int backoff_ms = 10;
    for (;;) {
      if (::connect(fd_, reinterpret_cast<sockaddr*>(&addr),
                    sizeof(addr)) == 0) {
        connected_ = true;
        break;
      }
      if (std::chrono::steady_clock::now() >= cdeadline) break;
      std::this_thread::sleep_for(std::chrono::milliseconds(backoff_ms));
      backoff_ms = std::min(backoff_ms * 2, 1000);
      ::close(fd_);
      fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    }
    if (!connected_) {
      std::ostringstream o;
      o << "could not connect to coordinator at " << host << ":" << port
        << " within HVD_COORD_CONNECT_TIMEOUT=" << connect_budget
        << " s (coordinator not started, wrong HVD_COORD_ADDR, or rank 0 "
        << "crashed during bring-up?)";
      init_error_ = o.str();
      return;
    }
    int one = 1;
    setsockopt(fd_, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    int32_t ver = kProtocolVersion;
    int32_t pport = peer_port_;
    std::string hello;
    hello.append(reinterpret_cast<char*>(&rank_), 4);
    hello.append(reinterpret_cast<char*>(&size_), 4);
    hello.append(reinterpret_cast<char*>(&ver), 4);
    hello.append(reinterpret_cast<char*>(&pport), 4);
    // Optional suffix: explicit ring data-plane address for NAT or
    // multi-homed hosts where the coordinator's getpeername() view of us
    // is not reachable by our ring neighbors ("ip" or "ip:port").
    // Validate the IPv4 literal HERE (same loud-rejection standard as
    // ParseEnvI64): a hostname or typo would otherwise zero out
    // inet_pton in every peer's connector and surface 30 s later as a
    // generic TransportError pointing nowhere.
    if (const char* adv = getenv("HOROVOD_RING_ADVERTISE_ADDR")) {
      std::string a(adv);
      // Shared with the coordinator's hello-side re-validation
      // (ValidAdvertiseAddr): both ends must agree on what is
      // well-formed, or a value one side accepts gets rejected (or
      // distributed) by the other. The port must parse fully and fit
      // uint16, or the peers' connectors would atoi a prefix and burn
      // the full IO timeout connecting to the wrong port.
      if (!ValidAdvertiseAddr(a)) {
        fprintf(stderr,
                "hvdcoord: ignoring malformed HOROVOD_RING_ADVERTISE_ADDR"
                "=\"%s\" (expected an IPv4 literal \"a.b.c.d\" or "
                "\"a.b.c.d:port\" with port 1-65535; hostnames are not "
                "resolved) — falling back to the getpeername-derived "
                "address\n",
                adv);
      } else {
        hello.append(a);
      }
    }
    SendFrame(fd_, send_mu_, hello);
    // Synchronous ack: the coordinator validates {rank, size, version}
    // before admitting us — misconfigured worlds fail HERE with a message,
    // not minutes later with a hang.
    std::string ackbody;
    if (!RecvFrame(fd_, &ackbody) || ackbody.empty() ||
        static_cast<MsgTag>(ackbody[0]) != MsgTag::kHelloAck) {
      init_error_ = "coordinator closed the connection during handshake";
      connected_ = false;
      return;
    }
    Reader rd(ackbody);
    rd.GetU8();  // tag
    bool ok = rd.GetU8() != 0;
    std::string msg = rd.GetStr();
    if (!ok) {
      init_error_ = msg;
      connected_ = false;
      return;
    }
    recv_thread_ = std::thread(&Client::RecvLoop, this);
    if (heartbeat_timeout_ > 0) {
      last_ack_ms_.store(NowMs());
      hb_thread_ = std::thread(&Client::HeartbeatLoop, this);
    }
  }

 public:
  const std::string& init_error() const { return init_error_; }

 private:
  std::string init_error_;

 public:

  ~Client() { Shutdown(); }

  bool connected() const { return connected_; }

  void Shutdown() {
    if (shutdown_.exchange(true)) return;
    if (connected_) {
      Buf b;
      b.PutU8(static_cast<uint8_t>(MsgTag::kShutdown));
      SendFrame(fd_, send_mu_, b.str());
    }
    {
      // Wake any waiters so they observe shutdown instead of blocking.
      std::lock_guard<std::mutex> l(mu_);
      cv_.notify_all();
    }
    if (hb_thread_.joinable()) hb_thread_.join();
    if (recv_thread_.joinable()) recv_thread_.join();
    if (fd_ >= 0) {
      ::close(fd_);
      fd_ = -1;
    }
    ClosePeerFds();  // recv thread has exited; safe to own the table now
    if (peer_listen_fd_ >= 0) { ::close(peer_listen_fd_); peer_listen_fd_ = -1; }
  }

  bool Enqueue(const Request& req) {
    if (!connected_) return false;
    return SendFrame(fd_, send_mu_, EncodeRequest(req));
  }

  // Whether the client-to-client data plane can run on this rank (the
  // ephemeral peer-listen socket bound successfully at init).
  bool peer_plane_available() const { return peer_listen_fd_ >= 0; }

  // Enqueue with ring election: a large collective is announced WITHOUT
  // its payload (k*Ring); the bytes stay here until the coordinator's
  // ring/mesh plan arrives, then move client-to-client. Everything else
  // takes the star. `flags` is the per-call plane override (the analog of
  // the reference's per-call device_dense=/device_sparse= placement knobs,
  // horovod/tensorflow/__init__.py:43-55): 0 = auto (threshold), 1 =
  // force star, 2 = force the peer plane regardless of payload size.
  // At world size 1 every plane is the identity (there are no peers to
  // move bytes between), so flags==2 is trivially satisfied by the local
  // path rather than a silent degrade — only an UNAVAILABLE peer plane at
  // size > 1 is an error, reported by hvdcoord_submit before this runs.
  bool Submit(Request req, int flags = 0) {
    bool kind_ringable =
        (req.type == ReqType::kAllreduce ||
         req.type == ReqType::kAllgather ||
         req.type == ReqType::kAlltoall ||
         req.type == ReqType::kReducescatter ||
         (req.type == ReqType::kBroadcast && req.root_rank == rank_)) &&
        size_ > 1 && peer_listen_fd_ >= 0;
    bool ringable;
    if (flags == 1) {
      ringable = false;
    } else if (flags == 2) {
      ringable = kind_ringable;
    } else {
      ringable = kind_ringable && ring_threshold_ > 0 &&
                 static_cast<int64_t>(req.payload.size()) >= ring_threshold_;
    }
    if (ringable) {
      {
        std::lock_guard<std::mutex> l(ring_mu_);
        ring_pending_[req.name] = RingWork{std::move(req.payload),
                                           req.dtype, req.red_op,
                                           req.shape};
      }
      switch (req.type) {
        case ReqType::kAllreduce: req.type = ReqType::kAllreduceRing; break;
        case ReqType::kAllgather: req.type = ReqType::kAllgatherRing; break;
        case ReqType::kBroadcast: req.type = ReqType::kBroadcastRing; break;
        case ReqType::kAlltoall: req.type = ReqType::kAlltoallRing; break;
        case ReqType::kReducescatter:
          req.type = ReqType::kReducescatterRing;
          break;
        default: break;
      }
      req.payload.clear();
      if (!Enqueue(req)) {
        std::lock_guard<std::mutex> l(ring_mu_);
        ring_pending_.erase(req.name);
        return false;
      }
      return true;
    }
    return Enqueue(req);
  }

  // Blocks until the named op completes. Returns 0 ok, 1 connection lost,
  // 2 stall deadline exceeded (HOROVOD_STALL_TIMEOUT strict mode; 0=off —
  // then this blocks forever like the reference, which only warns),
  // 3 world aborted (a worker or the coordinator died; message in
  // abort_message()).
  int Wait(const std::string& name, Response* out) {
    std::unique_lock<std::mutex> l(mu_);
    auto ready = [&] {
      return completed_.count(name) > 0 || dead_ || aborted_;
    };
    if (stall_timeout_secs_ > 0) {
      if (!cv_.wait_for(
              l, std::chrono::duration<double>(stall_timeout_secs_),
              ready)) {
        // Abandon the op: names are auto-generated and never waited
        // again, so a late-arriving response must be dropped on receipt
        // or it would sit in completed_ forever (the documented
        // continue-after-StalledError usage would leak every payload).
        abandoned_.insert(name);
        return 2;
      }
    } else {
      cv_.wait(l, ready);
    }
    // Deliver a completed result even under abort: the response arrived
    // before the failure, so the caller's data is intact.
    if (completed_.count(name) > 0) {
      *out = std::move(completed_[name]);
      completed_.erase(name);
      return 0;
    }
    if (aborted_) return 3;
    return 1;
  }

  // Whether the world has been aborted (worker/coordinator failure); the
  // diagnostic names the dead party. Submits and waits fail fast once set.
  bool aborted() {
    std::lock_guard<std::mutex> l(mu_);
    return aborted_;
  }
  std::string abort_message() {
    std::lock_guard<std::mutex> l(mu_);
    return abort_msg_;
  }

  // Fault-injection hook (HVD_FAULT_SPEC rank=N:mute@step=S): stop
  // beating so the coordinator sees this rank go silent while the process
  // — and its TCP socket — stays alive. The only way to exercise the
  // heartbeat-timeout path deterministically (a kill also closes the
  // socket, which trips the faster disconnect path instead).
  void set_heartbeat_mute(bool m) { hb_mute_.store(m); }

  // Pending live resize, if any: returns true and fills the triple when a
  // kResizeNotice (or ack piggyback) announced one. One relaxed atomic
  // load per call — cheap enough for every step boundary.
  bool pending_resize(int32_t* target, int32_t* port, int32_t* gen) {
    int32_t t = pending_resize_target_.load();
    if (t <= 0) return false;
    if (target) *target = t;
    if (port) *port = pending_resize_port_.load();
    if (gen) *gen = pending_resize_gen_.load();
    return true;
  }

 private:
  static int64_t NowMs() {
    return std::chrono::duration_cast<std::chrono::milliseconds>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
  }

  // Mark the world dead with a diagnostic and wake every waiter. Runs on
  // the recv thread (coordinator-sent kAbort) or the heartbeat thread
  // (missed acks) — first writer wins, the message is never overwritten.
  void Abort(const std::string& msg) {
    std::lock_guard<std::mutex> l(mu_);
    if (!aborted_) {
      aborted_ = true;
      abort_msg_ = msg;
    }
    cv_.notify_all();
  }

  // Client side of the liveness plane: beat every ~timeout/4; if the
  // coordinator has not acked for a full timeout it is dead or wedged —
  // abort locally so blocked waits fail over instead of hanging (the
  // symmetric half of the coordinator's CheckHeartbeats). A C++ thread:
  // keeps beating through long Python-side pauses (GIL-free), so a slow
  // JAX compile never reads as a dead rank.
  void HeartbeatLoop() {
    int64_t interval_ms =
        static_cast<int64_t>(heartbeat_timeout_ * 1000 / 4);
    if (interval_ms < 50) interval_ms = 50;
    if (interval_ms > 2000) interval_ms = 2000;
    while (!shutdown_.load()) {
      if (!hb_mute_.load()) {
        Buf b;
        b.PutU8(static_cast<uint8_t>(MsgTag::kHeartbeat));
        b.PutI32(rank_);
        SendFrame(fd_, send_mu_, b.str());  // EOF surfaces on recv thread
        int64_t silent_ms = NowMs() - last_ack_ms_.load();
        if (silent_ms >
            static_cast<int64_t>(heartbeat_timeout_ * 1000)) {
          std::ostringstream o;
          o << "coordinator failure: no heartbeat-ack from rank 0 for "
            << silent_ms / 1000.0 << " s (> HVD_HEARTBEAT_TIMEOUT="
            << heartbeat_timeout_ << " s); coordinator process dead or "
            << "wedged — aborting this rank";
          fprintf(stderr, "hvdcoord: rank %d: %s\n", rank_,
                  o.str().c_str());
          Abort(o.str());
          return;
        }
      }
      // Sleep in short slices so Shutdown() joins promptly.
      for (int64_t slept = 0; slept < interval_ms && !shutdown_.load();
           slept += 25)
        std::this_thread::sleep_for(std::chrono::milliseconds(25));
    }
  }

  // -- ring data plane -----------------------------------------------------
  // Chunked ring allreduce (reduce-scatter + allgather) among the clients,
  // the bandwidth-optimal exchange the reference gets from MPI_Allreduce's
  // internals: each rank sends 2·(N-1)/N · bytes regardless of world size.
  // Runs on the recv thread, in coordinator response order — every rank
  // executes ring ops in the same sequence, so rings cannot interleave or
  // deadlock across ops (the reference's PerformOperation ordering).

  struct RingWork {
    std::string payload;
    DType dtype;
    RedOp red_op;
    std::vector<int64_t> shape;  // own announced shape (row size for ragged)
  };

  // Establish one full-duplex data-plane socket per needed peer, cached in
  // peer_fds_ and reused across ops (ring neighbors and mesh partners
  // share the table). Deterministic pair rule: the LOWER rank connects,
  // the higher accepts — no duplicate cross-connections. Every rank
  // executes ring/mesh ops in coordinator response order, so establishment
  // is globally ordered and cannot interleave across ops.
  bool EnsurePeerFds(const std::vector<std::string>& peers,
                     const std::vector<int>& needed) {
    std::vector<int> to_connect, to_accept;
    for (int q : needed) {
      if (q == rank_ || peer_fds_[q] >= 0) continue;
      // Dedupe: at N=2 the ring's right and left neighbor are the SAME
      // rank — one full-duplex socket serves both directions; a duplicate
      // entry would spawn two connectors and desynchronize the pair.
      auto& side = rank_ < q ? to_connect : to_accept;
      bool dup = false;
      for (int e : side) dup = dup || e == q;
      if (!dup) side.push_back(q);
    }
    if (to_connect.empty() && to_accept.empty()) return true;

    // Connect-side peers (all higher-ranked): one helper thread each, with
    // NON-BLOCKING connects under a wall-clock deadline — a blackholed
    // peer (SYN dropped, no RST) would otherwise park each blocking
    // connect on the kernel's ~2 min SYN retry schedule and blow through
    // the documented HOROVOD_RING_IO_TIMEOUT bound by orders of magnitude.
    std::vector<int> connected(to_connect.size(), -1);
    std::vector<std::thread> connectors;
    for (size_t k = 0; k < to_connect.size(); k++) {
      connectors.emplace_back([&, k] {
        const std::string& addr = peers[to_connect[k]];
        size_t c = addr.rfind(':');
        std::string ip = addr.substr(0, c);
        int pport = atoi(addr.c_str() + c + 1);
        auto cdeadline = std::chrono::steady_clock::now() +
                         std::chrono::seconds(ring_io_secs_);
        while (std::chrono::steady_clock::now() < cdeadline) {
          int s = ::socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
          sockaddr_in a{};
          a.sin_family = AF_INET;
          a.sin_port = htons(static_cast<uint16_t>(pport));
          if (inet_pton(AF_INET, ip.c_str(), &a.sin_addr) != 1) {
            // Unresolvable peer address: retrying cannot help; fail the
            // op now with the cause on stderr instead of burning the
            // full IO timeout connecting to 0.0.0.0.
            fprintf(stderr,
                    "hvdcoord: rank %d has unparseable ring data-plane "
                    "address \"%s\" (check HOROVOD_RING_ADVERTISE_ADDR)\n",
                    to_connect[k], addr.c_str());
            ::close(s);
            return;
          }
          int rc = ::connect(s, reinterpret_cast<sockaddr*>(&a), sizeof(a));
          bool up = rc == 0;
          if (!up && errno == EINPROGRESS) {
            auto left_ms =
                std::chrono::duration_cast<std::chrono::milliseconds>(
                    cdeadline - std::chrono::steady_clock::now())
                    .count();
            pollfd pfd{s, POLLOUT, 0};
            if (left_ms > 0 &&
                ::poll(&pfd, 1, static_cast<int>(left_ms)) > 0) {
              int soerr = 0;
              socklen_t slen = sizeof(soerr);
              getsockopt(s, SOL_SOCKET, SO_ERROR, &soerr, &slen);
              up = soerr == 0;
            }
          }
          if (up) {
            // Back to blocking IO with the ring bound on both directions.
            int fl = fcntl(s, F_GETFL, 0);
            fcntl(s, F_SETFL, fl & ~O_NONBLOCK);
            int one = 1;
            setsockopt(s, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
            timeval io_timeout{ring_io_secs_, 0};
            setsockopt(s, SOL_SOCKET, SO_SNDTIMEO, &io_timeout,
                       sizeof(io_timeout));
            setsockopt(s, SOL_SOCKET, SO_RCVTIMEO, &io_timeout,
                       sizeof(io_timeout));
            int32_t me = rank_;
            if (::send(s, &me, 4, MSG_NOSIGNAL) == 4) {
              connected[k] = s;  // each thread writes its own slot
              return;
            }
            ::close(s);
            return;
          }
          ::close(s);
          std::this_thread::sleep_for(std::chrono::milliseconds(50));
        }
      });
    }

    // Accept-side peers (all lower-ranked; a plan means every rank got the
    // same response, so they are coming). Stray connections to the data
    // port (port scanners, probes) must not hang or kill the rank — same
    // hardening standard as the control-plane hello: bound the identity
    // read with a recv timeout, classify by identity, and keep accepting
    // until every expected peer shows up or the deadline passes.
    size_t missing = to_accept.size();
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::seconds(ring_io_secs_);
    while (missing > 0) {
      auto left_ms = std::chrono::duration_cast<std::chrono::milliseconds>(
                         deadline - std::chrono::steady_clock::now())
                         .count();
      if (left_ms <= 0) break;
      pollfd pfd{peer_listen_fd_, POLLIN, 0};
      if (::poll(&pfd, 1, static_cast<int>(left_ms)) <= 0) break;
      int fd = ::accept(peer_listen_fd_, nullptr, nullptr);
      if (fd < 0) break;
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      timeval id_timeout{/*tv_sec=*/5, /*tv_usec=*/0};
      setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &id_timeout,
                 sizeof(id_timeout));
      int32_t who = -1;
      bool expected = false;
      if (RecvAll(fd, &who, 4)) {
        for (int q : to_accept)
          expected = expected || (q == who && peer_fds_[who] < 0);
      }
      if (expected) {
        // Keep the IO bound for every future chunk send/recv: a peer
        // dying mid-op must surface as a failed step (-> TransportError),
        // not an unbounded block that also starves the control socket.
        timeval io_timeout{ring_io_secs_, 0};
        setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &io_timeout,
                   sizeof(io_timeout));
        setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &io_timeout,
                   sizeof(io_timeout));
        peer_fds_[who] = fd;
        missing--;
      } else {
        fprintf(stderr,
                "hvdcoord: rejecting stray connection on peer data port "
                "(got identity %d)\n", who);
        ::close(fd);  // stray/garbled: keep accepting
      }
    }
    for (auto& t : connectors) t.join();
    for (size_t k = 0; k < to_connect.size(); k++)
      if (connected[k] >= 0) peer_fds_[to_connect[k]] = connected[k];
    bool ok = missing == 0;
    for (int q : to_connect) ok = ok && peer_fds_[q] >= 0;
    return ok;
  }

  void ClosePeerFds() {
    for (int& fd : peer_fds_)
      if (fd >= 0) { ::close(fd); fd = -1; }
  }

  // Raw fixed-size exchange with two peers: send `snd` on snd_fd while
  // receiving `rcv_n` bytes from rcv_fd (for ring ops these are the right
  // and left neighbors; for the mesh alltoall, the step's partners — the
  // two may be the same full-duplex socket at N=2). The send rides a
  // helper thread so a full TCP buffer cannot deadlock the step (everyone
  // sends and receives simultaneously). Thread spawn cost (~10 us) is
  // noise against the >=MB-scale transfers the peer plane carries; both
  // sockets have HOROVOD_RING_IO_TIMEOUT bounds so a dead peer fails the
  // step.
  bool RingStep(int snd_fd, const char* snd, size_t snd_n, int rcv_fd,
                char* rcv, size_t rcv_n) {
    std::atomic<bool> send_ok{true};
    std::thread sender([&] {
      size_t off = 0;
      while (off < snd_n) {
        ssize_t n = ::send(snd_fd, snd + off, snd_n - off, MSG_NOSIGNAL);
        if (n <= 0) { send_ok.store(false); return; }
        off += static_cast<size_t>(n);
      }
    });
    bool recv_ok = rcv_n == 0 || RecvAll(rcv_fd, rcv, rcv_n);
    sender.join();
    if (send_ok.load()) ring_bytes_sent_ += snd_n;
    return send_ok.load() && recv_ok;
  }

  // Ring-neighbor convenience wrapper (send right, receive from left).
  bool NeighborStep(const char* snd, size_t snd_n, char* rcv, size_t rcv_n) {
    int right = (rank_ + 1) % size_;
    int left = (rank_ - 1 + size_) % size_;
    return RingStep(peer_fds_[right], snd, snd_n, peer_fds_[left], rcv,
                    rcv_n);
  }

  bool EnsureRingNeighbors(const std::vector<std::string>& peers) {
    std::vector<int> needed{(rank_ + 1) % size_, (rank_ - 1 + size_) % size_};
    return EnsurePeerFds(peers, needed);
  }

  bool RunRing(const Response& plan, RingWork work, std::string* out) {
    if (!EnsureRingNeighbors(plan.ring_peers)) return false;
    const int N = size_;
    std::string& buf = work.payload;
    const size_t esz = static_cast<size_t>(DTypeSize(work.dtype));
    const size_t elems = buf.size() / esz;
    // Element-aligned chunk boundaries [off[i], off[i+1]) in bytes.
    std::vector<size_t> off(N + 1);
    for (int i = 0; i <= N; i++)
      off[i] = (elems * i / N) * esz;
    std::string incoming(off[1] - off[0] + esz, '\0');  // max chunk size
    auto chunk = [&](int i) { return &buf[0] + off[i]; };
    auto clen = [&](int i) { return off[i + 1] - off[i]; };

    // Phase 1: reduce-scatter. After step s, the chunk received at
    // (r - s - 1) holds the partial sum of s+2 ranks; after N-2 steps rank
    // r owns the fully reduced chunk (r + 1) % N.
    for (int s = 0; s <= N - 2; s++) {
      int snd = (rank_ - s + N) % N;
      int rcv = (rank_ - s - 1 + N) % N;
      if (!NeighborStep(chunk(snd), clen(snd), &incoming[0], clen(rcv)))
        return false;
      // In-place accumulate; order differs from the star's rank-order
      // reduce only in float rounding (as MPI's ring does).
      ReducePayloadRaw(work.dtype, work.red_op, chunk(rcv), incoming.data(),
                       clen(rcv));
    }
    // Phase 2: allgather of the reduced chunks around the ring.
    for (int s = 0; s <= N - 2; s++) {
      int snd = (rank_ + 1 - s + N) % N;
      int rcv = (rank_ - s + N) % N;
      if (!NeighborStep(chunk(snd), clen(snd), &incoming[0], clen(rcv)))
        return false;
      memcpy(chunk(rcv), incoming.data(), clen(rcv));
    }
    ring_ops_++;
    *out = std::move(buf);
    return true;
  }

  // Ring reducescatter: the reduce-scatter PHASE of the ring allreduce
  // alone, with chunk indices shifted by -1 so rank r ends owning its own
  // fully-reduced block r (the psum_scatter tiled semantics the star path
  // implements host-side). Blocks are exact (first dim divisible by N,
  // validated at negotiation). Per-rank traffic = (N-1)/N · payload.
  bool RunRingScatter(const Response& plan, RingWork work,
                      std::string* out) {
    if (!EnsureRingNeighbors(plan.ring_peers)) return false;
    const int N = size_;
    std::string& buf = work.payload;
    const size_t block = buf.size() / N;
    std::string incoming(block, '\0');
    for (int s = 0; s <= N - 2; s++) {
      int snd = (rank_ - s - 1 + 2 * N) % N;
      int rcv = (rank_ - s - 2 + 2 * N) % N;
      if (!NeighborStep(&buf[snd * block], block, &incoming[0], block))
        return false;
      ReducePayloadRaw(work.dtype, work.red_op, &buf[rcv * block],
                       incoming.data(), block);
    }
    out->assign(buf.data() + rank_ * block, block);
    ring_ops_++;
    return true;
  }

  // Mesh alltoall: direct pairwise block exchange over the full-duplex
  // peer-socket mesh. At step d, send block (r+d) to rank (r+d) while
  // receiving block r of rank (r-d) from (r-d) — pairwise symmetric, so
  // RingStep's concurrent send+recv cannot deadlock. Per-rank traffic =
  // (N-1)/N · payload, independent of world size (the star relays
  // N · payload through rank 0 in each direction).
  bool RunMeshAlltoall(const Response& plan, RingWork work,
                       std::string* out) {
    std::vector<int> needed;
    for (int q = 0; q < size_; q++)
      if (q != rank_) needed.push_back(q);
    if (!EnsurePeerFds(plan.ring_peers, needed)) return false;
    const size_t block = work.payload.size() / size_;
    out->assign(work.payload.size(), '\0');
    memcpy(&(*out)[0] + rank_ * block, work.payload.data() + rank_ * block,
           block);
    for (int d = 1; d < size_; d++) {
      int to = (rank_ + d) % size_;
      int from = (rank_ - d + size_) % size_;
      if (!RingStep(peer_fds_[to], work.payload.data() + to * block, block,
                    peer_fds_[from], &(*out)[0] + from * block, block))
        return false;
    }
    ring_ops_++;
    return true;
  }

  // Ring allgather: each rank's (possibly ragged) block circulates N-1
  // hops; at step s we forward the block received at step s-1 while
  // writing the incoming one straight into its slot of the final
  // rank-ordered concatenation. Per-rank traffic = output - own block.
  bool RunRingGather(const Response& plan, RingWork work,
                     std::string* out) {
    if (!EnsureRingNeighbors(plan.ring_peers)) return false;
    const int N = size_;
    int64_t row_bytes = static_cast<int64_t>(DTypeSize(work.dtype));
    for (size_t i = 1; i < work.shape.size(); i++)
      row_bytes *= work.shape[i];
    std::vector<int64_t> nb(N), off(N + 1, 0);
    for (int i = 0; i < N; i++) {
      nb[i] = plan.sizes[i] * row_bytes;
      off[i + 1] = off[i] + nb[i];
    }
    out->assign(static_cast<size_t>(off[N]), '\0');
    memcpy(&(*out)[0] + off[rank_], work.payload.data(),
           work.payload.size());
    for (int s = 0; s <= N - 2; s++) {
      int snd = (rank_ - s + N) % N;
      int rcv = (rank_ - s - 1 + N) % N;
      if (!NeighborStep(out->data() + off[snd],
                        static_cast<size_t>(nb[snd]),
                        &(*out)[0] + off[rcv], static_cast<size_t>(nb[rcv])))
        return false;
    }
    ring_ops_++;
    return true;
  }

  // Ring broadcast: chunk-pipelined CHAIN from the root around the rank
  // ring (root -> root+1 -> ... -> root-1). Middle ranks forward chunk
  // c-1 while receiving chunk c (RingStep's simultaneous send+recv), so
  // the payload streams down the chain at link bandwidth; per-link bytes
  // = payload exactly.
  bool RunRingBcast(const Response& plan, std::string root_payload,
                    std::string* out) {
    if (!EnsureRingNeighbors(plan.ring_peers)) return false;
    int root = static_cast<int>(plan.sizes.empty() ? 0 : plan.sizes[0]);
    int64_t total = DTypeSize(plan.dtype);
    for (int64_t d : plan.shape) total *= d;
    const size_t kChunk = 1 << 20;
    bool is_last = rank_ == (root - 1 + size_) % size_;
    if (rank_ == root) {
      *out = std::move(root_payload);
      for (size_t o = 0; o < static_cast<size_t>(total); o += kChunk) {
        size_t l = std::min(kChunk, static_cast<size_t>(total) - o);
        if (!NeighborStep(out->data() + o, l, nullptr, 0)) return false;
      }
    } else {
      out->assign(static_cast<size_t>(total), '\0');
      size_t po = 0, pl = 0;
      for (size_t o = 0; o < static_cast<size_t>(total); o += kChunk) {
        size_t l = std::min(kChunk, static_cast<size_t>(total) - o);
        // Forward the previous chunk while receiving this one.
        if (!NeighborStep(is_last ? nullptr : out->data() + po,
                          is_last ? 0 : pl, &(*out)[0] + o, l))
          return false;
        po = o;
        pl = l;
      }
      if (!is_last && pl > 0) {
        if (!NeighborStep(out->data() + po, pl, nullptr, 0)) return false;
      }
    }
    ring_ops_++;
    return true;
  }

  void RecvLoop() {
    while (!shutdown_.load()) {
      std::string body;
      if (!RecvFrame(fd_, &body)) break;
      Reader rd(body);
      MsgTag tag = static_cast<MsgTag>(rd.GetU8());
      if (tag == MsgTag::kHeartbeatAck) {
        last_ack_ms_.store(NowMs());
        // v7 acks carry the pending-resize triple; reading it here means
        // the training loop's step-boundary poll is one atomic load.
        if (body.size() >= 13) {
          int32_t target = rd.GetI32();
          int32_t port = rd.GetI32();
          int32_t gen = rd.GetI32();
          if (target > 0) SetPendingResize(target, port, gen);
        }
        continue;
      }
      if (tag != MsgTag::kResponse) break;
      Response resp = DecodeResponse(rd);
      if (resp.type == RespType::kShutdown) break;
      if (resp.type == RespType::kResizeNotice) {
        if (resp.sizes.size() >= 3)
          SetPendingResize(static_cast<int32_t>(resp.sizes[0]),
                           static_cast<int32_t>(resp.sizes[1]),
                           static_cast<int32_t>(resp.sizes[2]));
        continue;
      }
      if (resp.type == RespType::kAbort) {
        // World aborted (a rank died / went silent). Drop the ring
        // stashes — their plans will never arrive — and fail every
        // current and future wait with the named dead rank.
        {
          std::lock_guard<std::mutex> l(ring_mu_);
          ring_pending_.clear();
        }
        Abort(resp.error);
        break;
      }
      if (resp.type == RespType::kResubmitStar) {
        // Mixed straddling-threshold allgather: re-announce with the
        // stashed payload over the star plane.
        RingWork work;
        {
          std::lock_guard<std::mutex> l(ring_mu_);
          auto it = ring_pending_.find(resp.name);
          if (it == ring_pending_.end()) break;  // protocol violation
          work = std::move(it->second);
          ring_pending_.erase(it);
        }
        Request rq;
        rq.rank = rank_;
        rq.type = ReqType::kAllgather;
        rq.dtype = work.dtype;
        rq.red_op = work.red_op;
        rq.shape = work.shape;
        rq.name = resp.name;
        rq.payload = std::move(work.payload);
        if (!Enqueue(rq)) break;
        continue;
      }
      if (resp.type == RespType::kBroadcastRing) {
        std::string stash;  // only the root has one
        {
          std::lock_guard<std::mutex> l(ring_mu_);
          auto it = ring_pending_.find(resp.name);
          if (it != ring_pending_.end()) {
            stash = std::move(it->second.payload);
            ring_pending_.erase(it);
          }
        }
        std::string result;
        if (!RunRingBcast(resp, std::move(stash), &result)) break;
        resp.type = RespType::kBroadcast;
        resp.payload = std::move(result);
        resp.sizes.clear();
      } else if (resp.type == RespType::kAllgatherRing) {
        RingWork work;
        {
          std::lock_guard<std::mutex> l(ring_mu_);
          auto it = ring_pending_.find(resp.name);
          if (it == ring_pending_.end()) break;  // protocol violation
          work = std::move(it->second);
          ring_pending_.erase(it);
        }
        std::string gathered;
        if (!RunRingGather(resp, std::move(work), &gathered)) break;
        resp.type = RespType::kAllgather;  // sizes already negotiated
        resp.payload = std::move(gathered);
      } else if (resp.type == RespType::kAlltoallRing) {
        RingWork work;
        {
          std::lock_guard<std::mutex> l(ring_mu_);
          auto it = ring_pending_.find(resp.name);
          if (it == ring_pending_.end()) break;  // protocol violation
          work = std::move(it->second);
          ring_pending_.erase(it);
        }
        std::string exchanged;
        if (!RunMeshAlltoall(resp, std::move(work), &exchanged)) break;
        resp.type = RespType::kAlltoall;
        resp.payload = std::move(exchanged);
      } else if (resp.type == RespType::kReducescatterRing) {
        RingWork work;
        {
          std::lock_guard<std::mutex> l(ring_mu_);
          auto it = ring_pending_.find(resp.name);
          if (it == ring_pending_.end()) break;  // protocol violation
          work = std::move(it->second);
          ring_pending_.erase(it);
        }
        std::string scattered;
        if (!RunRingScatter(resp, std::move(work), &scattered)) break;
        resp.type = RespType::kReducescatter;
        resp.payload = std::move(scattered);
      } else if (resp.type == RespType::kAllreduceRing) {
        // NB: a ring op whose wait stall-timed-out keeps its stash here
        // until the plan (or an error) arrives — if the slow ranks do
        // announce late, the world still needs this rank's payload to
        // complete the ring (the result is then dropped via abandoned_).
        // A never-completing op retains its payload until shutdown; that
        // retention is the price of not corrupting a late completion.
        RingWork work;
        {
          std::lock_guard<std::mutex> l(ring_mu_);
          auto it = ring_pending_.find(resp.name);
          if (it == ring_pending_.end()) break;  // protocol violation
          work = std::move(it->second);
          ring_pending_.erase(it);
        }
        std::string reduced;
        if (!RunRing(resp, std::move(work), &reduced)) break;
        resp.type = RespType::kAllreduce;
        resp.payload = std::move(reduced);
      } else if (resp.type == RespType::kError) {
        // A rejected ring announcement still holds the stashed payload.
        std::lock_guard<std::mutex> l(ring_mu_);
        ring_pending_.erase(resp.name);
      }
      std::lock_guard<std::mutex> l(mu_);
      responses_received_++;
      // Late response to a wait that already timed out (strict stall
      // mode): count it completed but drop the payload — nobody will
      // ever redeem it.
      auto deliver = [&](Response&& one) {
        ops_completed_++;
        if (abandoned_.erase(one.name) > 0) return;
        completed_[one.name] = std::move(one);
      };
      if (!resp.fused_names.empty()) {
        // Fused frame: split the concatenated payload back into the
        // individual ops it answers (reference: one MPIResponse completes
        // every entry in tensor_names, mpi_ops.cc:1024-1096 memcpy-out).
        size_t off = 0;
        for (size_t i = 0; i < resp.fused_names.size(); i++) {
          Response one;
          one.type = resp.type;
          one.name = resp.fused_names[i];
          size_t n = static_cast<size_t>(resp.fused_nbytes[i]);
          one.payload = resp.payload.substr(off, n);
          off += n;
          deliver(std::move(one));
        }
      } else {
        deliver(std::move(resp));
      }
      cv_.notify_all();
    }
    // Close the peer sockets on the way out so peers blocked in a
    // ring/mesh step observe EOF immediately (fast failure cascade)
    // instead of waiting out their IO timeout.
    ClosePeerFds();
    std::lock_guard<std::mutex> l(mu_);
    dead_ = true;
    cv_.notify_all();
  }

 public:
  // Stats for fusion observability (tested by the fused-path analog of
  // mpi_ops_test.py:116-148): frames received vs ops completed — completed >
  // received proves response fusion happened.
  long long responses_received() {
    std::lock_guard<std::mutex> l(mu_);
    return responses_received_;
  }
  long long ops_completed() {
    std::lock_guard<std::mutex> l(mu_);
    return ops_completed_;
  }
  // Ring observability (the byte-accounting proof that large allreduces
  // move <= ~2x bytes per rank regardless of world size).
  long long ring_ops() { return ring_ops_.load(); }
  long long ring_bytes_sent() { return ring_bytes_sent_.load(); }

 private:
  long long responses_received_ = 0;
  long long ops_completed_ = 0;
  std::atomic<long long> ring_ops_{0};
  std::atomic<long long> ring_bytes_sent_{0};

  int32_t rank_;
  int size_;
  int fd_ = -1;
  bool connected_ = false;
  int64_t ring_threshold_ = 0;
  double stall_timeout_secs_ = 0;
  int ring_io_secs_ = 30;
  double heartbeat_timeout_ = 30.0;
  std::thread hb_thread_;
  std::atomic<bool> hb_mute_{false};
  std::atomic<int64_t> last_ack_ms_{0};
  // Pending live resize announced by the coordinator (v7). Port/gen are
  // written before target (the readiness flag), so a reader that sees the
  // target also sees its port/generation.
  std::atomic<int32_t> pending_resize_target_{0};
  std::atomic<int32_t> pending_resize_port_{0};
  std::atomic<int32_t> pending_resize_gen_{0};

  void SetPendingResize(int32_t target, int32_t port, int32_t gen) {
    pending_resize_port_.store(port);
    pending_resize_gen_.store(gen);
    pending_resize_target_.store(target);
  }
  int peer_listen_fd_ = -1;
  int peer_port_ = 0;
  // Full-duplex data-plane socket per peer rank (-1 = not established).
  // Owned by the recv thread (all ring/mesh ops run there in response
  // order); Shutdown touches it only after joining that thread.
  std::vector<int> peer_fds_;
  std::mutex ring_mu_;
  std::map<std::string, RingWork> ring_pending_;
  std::mutex send_mu_;
  std::thread recv_thread_;
  std::atomic<bool> shutdown_{false};
  std::mutex mu_;
  std::condition_variable cv_;
  std::map<std::string, Response> completed_;
  std::set<std::string> abandoned_;  // stall-timed-out names (guarded by mu_)
  bool dead_ = false;
  bool aborted_ = false;        // guarded by mu_
  std::string abort_msg_;       // guarded by mu_
};

// ---------------------------------------------------------------------------
// Global state + C ABI (parity: horovod_tensorflow_* C functions,
// mpi_ops.cc:1516-1566; single-owner global like HorovodGlobalState).
// ---------------------------------------------------------------------------

struct Global {
  std::unique_ptr<Coordinator> coordinator;
  std::unique_ptr<Client> client;
  int rank = -1;
  int size = 0;
  std::mutex mu;
};

Global* g() {
  static Global instance;
  return &instance;
}

}  // namespace hvdcoord

extern "C" {

// Returns 0 on success; 1 coordinator bind failure; 2 connect/handshake
// failure (message in err — e.g. world-size or protocol-version mismatch
// detected by the coordinator's hello validation).
int hvdcoord_init(int rank, int size, const char* host, int port,
                  long long fusion_threshold, double stall_secs,
                  const char* timeline_path, char* err, int errlen) {
  using namespace hvdcoord;
  std::lock_guard<std::mutex> l(g()->mu);
  if (g()->client) return 0;  // idempotent (InitializeHorovodOnce parity)
  if (rank == 0) {
    g()->coordinator.reset(new Coordinator(
        size, port, fusion_threshold, stall_secs,
        timeline_path ? timeline_path : ""));
    if (!g()->coordinator->ok()) {
      if (err && errlen > 0)
        snprintf(err, errlen, "coordinator failed to bind/listen on port %d",
                 port);
      return 1;
    }
  }
  g()->client.reset(new Client(rank, size, host, port));
  if (!g()->client->connected()) {
    if (err && errlen > 0) {
      const std::string& m = g()->client->init_error();
      snprintf(err, errlen, "%s",
               m.empty() ? "could not connect to coordinator" : m.c_str());
    }
    g()->client.reset();
    g()->coordinator.reset();
    return 2;
  }
  g()->rank = rank;
  g()->size = size;
  return 0;
}

int hvdcoord_rank() { return hvdcoord::g()->client ? hvdcoord::g()->rank : -1; }
int hvdcoord_size() { return hvdcoord::g()->client ? hvdcoord::g()->size : -1; }

// Non-blocking submit (reference: ComputeAsync + EnqueueTensor*,
// mpi_ops.cc:1752-1772 — many collectives negotiate concurrently, feeding
// coordinator-side fusion). `plane` is the per-call placement override
// (the analog of the reference's device_dense=/device_sparse= knobs,
// horovod/tensorflow/__init__.py:43-55): 0 auto (HOROVOD_RING_THRESHOLD
// decides), 1 force the coordinator star, 2 force the client-to-client
// peer plane. Returns 0 ok, 2 transport failure.
int hvdcoord_submit(const char* name, int req_type, int dtype, int red_op,
                    int root_rank, int ndim, const long long* shape,
                    const void* data, long long nbytes, int plane,
                    char* err, int errlen) {
  using namespace hvdcoord;
  auto* G = g();
  if (!G->client) {
    snprintf(err, errlen, "hvdcoord not initialized");
    return 2;
  }
  if (G->client->aborted()) {
    // Fail fast: after a world abort every collective is doomed — a
    // fresh submit would announce into a dead coordinator and hang the
    // caller in wait. Surface the original failure instead.
    snprintf(err, errlen, "%s", G->client->abort_message().c_str());
    return 4;
  }
  Request req;
  req.rank = G->rank;
  req.type = static_cast<ReqType>(req_type);
  req.dtype = static_cast<DType>(dtype);
  req.red_op = static_cast<RedOp>(red_op);
  req.root_rank = root_rank;
  for (int i = 0; i < ndim; i++) req.shape.push_back(shape[i]);
  req.name = name;
  if (data && nbytes > 0)
    req.payload.assign(reinterpret_cast<const char*>(data),
                       static_cast<size_t>(nbytes));
  if (plane == 2 && G->size > 1 && !G->client->peer_plane_available()) {
    // An explicit force must not silently degrade to the star: the other
    // ranks would announce the ring variant and the world would fail with
    // a misattributed cross-rank mismatch error. Name the real cause.
    // (At size 1 every plane is the identity — no peers, nothing to
    // degrade — so the force is trivially satisfied, not an error.)
    snprintf(err, errlen,
             "plane=\"ring\" forced but the peer data plane is unavailable "
             "on rank %d (the ephemeral peer-listen socket failed to bind "
             "at init — port exhaustion?)",
             G->rank);
    return 2;
  }
  if (!G->client->Submit(std::move(req), plane)) {
    snprintf(err, errlen, "hvdcoord: send failed (coordinator down?)");
    return 2;
  }
  return 0;
}

// Block until the named op completes. Returns:
//   0 ok; fills *out (malloc'd; caller frees via hvdcoord_free), *out_nbytes,
//     and for allgather writes per-rank first dims into sizes_out[size].
//   1 coordinator-reported validation error (message in err, FailedPrecondition
//     parity, mpi_ops.cc:1141-1148); 2 transport failure; 3 stall deadline
//     exceeded (HOROVOD_STALL_TIMEOUT strict mode -> StalledError);
//   4 world aborted — a worker or the coordinator died (message names the
//     dead party -> WorkerFailureError).
int hvdcoord_wait(const char* name, void** out, long long* out_nbytes,
                  long long* sizes_out, char* err, int errlen) {
  using namespace hvdcoord;
  auto* G = g();
  if (!G->client) {
    snprintf(err, errlen, "hvdcoord not initialized");
    return 2;
  }
  Response resp;
  int wrc = G->client->Wait(name, &resp);
  if (wrc == 3) {
    snprintf(err, errlen, "%s", G->client->abort_message().c_str());
    return 4;
  }
  if (wrc == 2) {
    snprintf(err, errlen,
             "collective %s exceeded HOROVOD_STALL_TIMEOUT: one or more "
             "ranks never announced it (see the coordinator's stall "
             "warning for the ready-rank list)",
             name);
    return 3;
  }
  if (wrc != 0) {
    snprintf(err, errlen, "hvdcoord: connection lost while waiting for %s",
             name);
    return 2;
  }
  if (resp.type == RespType::kError) {
    snprintf(err, errlen, "%s", resp.error.c_str());
    return 1;
  }
  *out_nbytes = static_cast<long long>(resp.payload.size());
  *out = malloc(resp.payload.size() ? resp.payload.size() : 1);
  memcpy(*out, resp.payload.data(), resp.payload.size());
  if (sizes_out) {
    for (size_t i = 0; i < resp.sizes.size() && i < (size_t)G->size; i++)
      sizes_out[i] = resp.sizes[i];
  }
  return 0;
}

// Submit + wait (synchronous eager calls).
int hvdcoord_run(const char* name, int req_type, int dtype, int red_op,
                 int root_rank, int ndim, const long long* shape,
                 const void* data, long long nbytes, void** out,
                 long long* out_nbytes, long long* sizes_out, char* err,
                 int errlen) {
  int rc = hvdcoord_submit(name, req_type, dtype, red_op, root_rank, ndim,
                           shape, data, nbytes, /*plane=*/0, err, errlen);
  if (rc != 0) return rc;
  return hvdcoord_wait(name, out, out_nbytes, sizes_out, err, errlen);
}

// Fusion observability: response frames received vs ops completed on this
// rank's client (completed > received ⇔ some frames were fused).
long long hvdcoord_responses_received() {
  using namespace hvdcoord;
  return g()->client ? g()->client->responses_received() : -1;
}
long long hvdcoord_ops_completed() {
  using namespace hvdcoord;
  return g()->client ? g()->client->ops_completed() : -1;
}

// Ring-plane observability: ops that took the client-to-client ring, and
// the data-plane bytes this rank sent for them (2·(N-1)/N · payload per op
// — the bandwidth-optimality proof, independent of world size).
long long hvdcoord_ring_ops() {
  using namespace hvdcoord;
  return g()->client ? g()->client->ring_ops() : -1;
}
long long hvdcoord_ring_bytes_sent() {
  using namespace hvdcoord;
  return g()->client ? g()->client->ring_bytes_sent() : -1;
}

void hvdcoord_free(void* p) { free(p); }

// ---------------------------------------------------------------------------
// Deterministic fault-injection hooks (HVD_FAULT_SPEC; testing/faults.py).
// These simulate SILENT failures — the kind a kill cannot produce because
// the kernel closes a dead process's sockets (tripping the faster
// disconnect path). No-ops when the world is not initialized.
// ---------------------------------------------------------------------------

// Stop (1) / resume (0) this rank's heartbeats while keeping the process
// and socket alive: the coordinator must declare this rank dead after
// HVD_HEARTBEAT_TIMEOUT and abort the world.
void hvdcoord_mute_heartbeats(int mute) {
  using namespace hvdcoord;
  if (g()->client) g()->client->set_heartbeat_mute(mute != 0);
}

// Stop (1) / resume (0) the coordinator's heartbeat-acks (rank 0 only;
// no-op elsewhere): every client must independently detect the silent
// coordinator and abort after HVD_HEARTBEAT_TIMEOUT.
void hvdcoord_coord_mute_acks(int mute) {
  using namespace hvdcoord;
  if (g()->coordinator) g()->coordinator->set_mute_acks(mute != 0);
}

// Whether this rank's world has aborted (1) — test/observability hook.
int hvdcoord_aborted() {
  using namespace hvdcoord;
  return (g()->client && g()->client->aborted()) ? 1 : 0;
}

// Pending live resize announced over the v7 admin plane: returns 1 and
// fills {target world, new coordinator port, generation} when one is
// pending, 0 otherwise. One atomic load — called at every training step
// boundary by horovod_tpu.elastic.ResizeCoordinator.
int hvdcoord_pending_resize(int* target, int* port, int* generation) {
  using namespace hvdcoord;
  if (!g()->client) return 0;
  int32_t t = 0, p = 0, gen = 0;
  if (!g()->client->pending_resize(&t, &p, &gen)) return 0;
  if (target) *target = t;
  if (port) *port = p;
  if (generation) *generation = gen;
  return 1;
}

void hvdcoord_shutdown() {
  using namespace hvdcoord;
  std::lock_guard<std::mutex> l(g()->mu);
  if (g()->client) g()->client->Shutdown();
  g()->client.reset();
  g()->coordinator.reset();
}

}  // extern "C"
