// Native bulk IO for the client<->chunkserver data plane.
//
// Python's asyncio handles the control plane well, but shoveling 64 KiB
// data pieces through per-message Python objects caps the data plane.
// These functions run an ENTIRE part read or write-stream exchange in
// C++ over a blocking socket — framing, piece CRC verification/
// generation, buffer scatter — and are called from worker threads with
// the GIL released (ctypes does this automatically for plain C calls).
//
// Wire format (keep in sync with lizardfs_tpu/proto — the
// `lizardfs-lint` native-wire checker cross-checks these declarations
// against the catalog; bytes/str/list fields are u32-length/count-
// prefixed per proto/codec.py, trailing skew-tolerant fields like
// trace_id may be elided on the wire):
//   frame   = header type:u32 BE + length:u32 BE + version:u8 + body
//   CltocsRead(1200): req_id:u32 chunk_id:u64 version:u32 part_id:u32
//                     offset:u32 size:u32 trace_id:u64
//   CstoclReadData(1201): req_id:u32 chunk_id:u64 offset:u32 crc:u32
//                         data:bytes
//   CstoclReadStatus(1202): req_id:u32 chunk_id:u64 status:u8
//   CltocsReadBulk(1206): req_id:u32 chunk_id:u64 version:u32 part_id:u32
//                         offset:u32 size:u32 trace_id:u64
//   CstoclReadBulkData(1207): req_id:u32 chunk_id:u64 status:u8 offset:u32
//                             crcs:list:u32 data:bytes
//   CltocsWriteData(1211): req_id:u32 chunk_id:u64 write_id:u32 block:u32
//                          offset:u32 crc:u32 data:bytes
//   CstoclWriteStatus(1212): req_id:u32 chunk_id:u64 write_id:u32 status:u8
//   CltocsWriteBulk(1214): req_id:u32 chunk_id:u64 write_id:u32
//                          part_offset:u32 crcs:list:u32 data:bytes
//   CltocsWriteBulkPart(1215): req_id:u32 chunk_id:u64 write_id:u32
//                              part_id:u32 part_offset:u32 crcs:list:u32
//                              data:bytes
//
// Return codes: 0 = OK; >0 = protocol status byte from the peer;
// -1 = socket error; -2 = protocol violation; -3 = CRC mismatch.

#include <cerrno>
#include <ctime>
#include <cstdint>
#include <cstring>
#include <vector>

#if defined(_WIN32)
#error "POSIX only"
#endif
#include <algorithm>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include "shm_ring.h"

extern "C" uint32_t lz_crc32(uint32_t crc, const uint8_t* data, size_t len);

namespace {

constexpr uint32_t kTypeRead = 1200;
constexpr uint32_t kTypeReadData = 1201;
constexpr uint32_t kTypeReadStatus = 1202;
constexpr uint32_t kTypeWriteData = 1211;
constexpr uint32_t kTypeWriteStatus = 1212;
constexpr uint8_t kProtoVersion = 1;
constexpr size_t kMaxPayload = 1u << 20;  // pieces are <= 64 KiB + header
constexpr uint32_t kBlockSize = 64 * 1024;

inline void put32(uint8_t* p, uint32_t v) {
    p[0] = v >> 24; p[1] = v >> 16; p[2] = v >> 8; p[3] = v;
}
inline void put64(uint8_t* p, uint64_t v) {
    put32(p, static_cast<uint32_t>(v >> 32));
    put32(p + 4, static_cast<uint32_t>(v));
}
inline uint32_t get32(const uint8_t* p) {
    return (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
           (uint32_t(p[2]) << 8) | uint32_t(p[3]);
}
inline uint64_t get64(const uint8_t* p) {
    return (uint64_t(get32(p)) << 32) | get32(p + 4);
}

bool send_all(int fd, const uint8_t* buf, size_t len) {
    while (len) {
        ssize_t n = ::send(fd, buf, len, 0);
        if (n <= 0) {
            if (n < 0 && (errno == EINTR)) continue;
            return false;
        }
        buf += n;
        len -= static_cast<size_t>(n);
    }
    return true;
}

int64_t steady_us() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000000 + ts.tv_nsec / 1000;
}

int64_t steady_ms() { return steady_us() / 1000; }

constexpr uint32_t kTypeWriteBulk = 1214;
constexpr uint32_t kTypeWriteBulkPart = 1215;

// One bulk-write frame header (type 1214): fixed fields + per-block
// CRC table + payload length. Shared by the single-part and the
// multi-part scatter paths so the layout lives in exactly one place.
void build_bulk_write_header(std::vector<uint8_t>& head, uint64_t chunk_id,
                             uint32_t write_id, uint64_t part_offset,
                             const uint8_t* payload, uint64_t len) {
    const uint32_t ncrcs =
        static_cast<uint32_t>((len + kBlockSize - 1) / kBlockSize);
    head.resize(8 + 25 + 4 * ncrcs + 4);
    const size_t body = head.size() - 8 + len;
    put32(head.data(), kTypeWriteBulk);
    put32(head.data() + 4, static_cast<uint32_t>(body));
    head[8] = kProtoVersion;
    put32(head.data() + 9, write_id);
    put64(head.data() + 13, chunk_id);
    put32(head.data() + 21, write_id);
    put32(head.data() + 25, static_cast<uint32_t>(part_offset));
    put32(head.data() + 29, ncrcs);
    for (uint32_t b = 0; b < ncrcs; ++b) {
        const uint64_t start = uint64_t(b) * kBlockSize;
        const uint32_t piece = static_cast<uint32_t>(
            std::min<uint64_t>(kBlockSize, len - start));
        put32(head.data() + 33 + 4 * b, lz_crc32(0, payload + start, piece));
    }
    put32(head.data() + 33 + 4 * ncrcs, static_cast<uint32_t>(len));
}

// Part-addressed bulk-write frame (type 1215): the 1214 layout with the
// target part_id inserted after write_id, so several parts of one chunk
// can multiplex a single connection (the server demuxes write sessions
// on (chunk_id, part_id) instead of assuming one part per connection).
void build_bulk_write_part_header(std::vector<uint8_t>& head,
                                  uint64_t chunk_id, uint32_t write_id,
                                  uint32_t part_id, uint64_t part_offset,
                                  const uint8_t* payload, uint64_t len) {
    const uint32_t ncrcs =
        static_cast<uint32_t>((len + kBlockSize - 1) / kBlockSize);
    head.resize(8 + 29 + 4 * ncrcs + 4);
    const size_t body = head.size() - 8 + len;
    put32(head.data(), kTypeWriteBulkPart);
    put32(head.data() + 4, static_cast<uint32_t>(body));
    head[8] = kProtoVersion;
    put32(head.data() + 9, write_id);
    put64(head.data() + 13, chunk_id);
    put32(head.data() + 21, write_id);
    put32(head.data() + 25, part_id);
    put32(head.data() + 29, static_cast<uint32_t>(part_offset));
    put32(head.data() + 33, ncrcs);
    for (uint32_t b = 0; b < ncrcs; ++b) {
        const uint64_t start = uint64_t(b) * kBlockSize;
        const uint32_t piece = static_cast<uint32_t>(
            std::min<uint64_t>(kBlockSize, len - start));
        put32(head.data() + 37 + 4 * b, lz_crc32(0, payload + start, piece));
    }
    put32(head.data() + 37 + 4 * ncrcs, static_cast<uint32_t>(len));
}

// Validate a CstoclWriteStatus payload: returns the peer status
// (0 = OK) or -2 on a protocol violation.
int parse_write_status(const uint8_t* pay, uint32_t len) {
    if (len < 18 || pay[0] != kProtoVersion) return -2;
    return pay[17];
}

// The same for a bulk write's ack, which must echo its write_id.
int parse_bulk_write_ack(const uint8_t* pay, uint32_t len,
                         uint32_t write_id) {
    if (len >= 18 && get32(pay + 13) != write_id) return -2;
    return parse_write_status(pay, len);
}

bool recv_all(int fd, uint8_t* buf, size_t len) {
    while (len) {
        ssize_t n = ::recv(fd, buf, len, 0);
        if (n <= 0) {
            if (n < 0 && (errno == EINTR)) continue;
            return false;
        }
        buf += n;
        len -= static_cast<size_t>(n);
    }
    return true;
}

}  // namespace

// Per-thread request trace id (runtime/tracing.py): the python caller
// sets it on the SAME executor thread right before the exchange, so
// the C request builders can append it as the optional trailing u64 of
// the request frame (wire.h trace contract) without signature churn.
// 0 (the default) keeps request frames byte-identical to pre-trace
// builds.
thread_local uint64_t g_trace_id = 0;
// the originating cluster session, same pattern (per-session op
// accounting on the chunkserver): appended AFTER the trace id — the
// server parses it positionally past the trace slot, so a session
// only rides frames that also carry a (nonzero) trace
thread_local uint64_t g_session_id = 0;

namespace {

constexpr uint32_t kTypeReadBulk = 1206;
constexpr uint32_t kTypeReadBulkData = 1207;

// One read request (CltocsRead or CltocsReadBulk: the same fields) on a
// blocking socket, the calling thread's trace and session ids riding
// it (the optional trailing fields).
bool send_read_request(int fd, uint32_t type, uint64_t chunk_id,
                       uint32_t version, uint32_t part_id, uint32_t offset,
                       uint32_t size) {
    uint8_t req[8 + 1 + 4 + 8 + 4 + 4 + 4 + 4 + 8 + 8];
    size_t body = 1 + 4 + 8 + 4 + 4 + 4 + 4;
    req[8] = kProtoVersion;
    put32(req + 9, 1);
    put64(req + 13, chunk_id);
    put32(req + 21, version);
    put32(req + 25, part_id);
    put32(req + 29, offset);
    put32(req + 33, size);
    if (g_trace_id != 0) {  // optional trailing trace + session (wire.h)
        put64(req + 37, g_trace_id);
        body += 8;
        if (g_session_id != 0) {
            put64(req + 45, g_session_id);
            body += 8;
        }
    }
    put32(req, type);
    put32(req + 4, static_cast<uint32_t>(body));
    return send_all(fd, req, 8 + body);
}

}  // namespace

extern "C" {

void lz_trace_set(uint64_t trace_id) { g_trace_id = trace_id; }

void lz_session_set(uint64_t session_id) { g_session_id = session_id; }

// Read [offset, offset+size) of one part into out. Whole exchange.
int lz_read_part(int fd, uint64_t chunk_id, uint32_t version,
                 uint32_t part_id, uint32_t offset, uint32_t size,
                 uint8_t* out) {
    if (!send_read_request(fd, kTypeRead, chunk_id, version, part_id, offset,
                           size))
        return -1;

    std::vector<uint8_t> payload(kMaxPayload);
    uint64_t received = 0;
    for (;;) {
        uint8_t header[8];
        if (!recv_all(fd, header, 8)) return -1;
        uint32_t type = get32(header);
        uint32_t length = get32(header + 4);
        if (length < 1 || length > kMaxPayload) return -2;
        if (length > payload.size()) payload.resize(length);
        if (!recv_all(fd, payload.data(), length)) return -1;
        const uint8_t* p = payload.data();
        if (p[0] != kProtoVersion) return -2;
        if (type == kTypeReadData) {
            if (length < 1 + 4 + 8 + 4 + 4 + 4) return -2;
            uint32_t piece_off = get32(p + 13);
            uint32_t crc = get32(p + 17);
            uint32_t dlen = get32(p + 21);
            if (1 + 4 + 8 + 4 + 4 + 4 + dlen != length) return -2;
            const uint8_t* data = p + 25;
            // Pieces must arrive in order and contiguously; a byte
            // counter alone would let overlapping pieces mask gaps of
            // uninitialized memory in the caller's buffer.
            if (piece_off != offset + received ||
                uint64_t(piece_off) + dlen > uint64_t(offset) + size)
                return -2;
            if (lz_crc32(0, data, dlen) != crc) return -3;
            std::memcpy(out + (piece_off - offset), data, dlen);
            received += dlen;
        } else if (type == kTypeReadStatus) {
            if (length < 14) return -2;
            uint8_t status = p[13];
            if (status != 0) return status;
            if (received < size) return -2;  // short read
            return 0;
        } else {
            return -2;
        }
    }
}

// Bulk read: one CstoclReadBulkData reply — CRC table + raw range —
// received DIRECTLY into the caller's buffer, then verified here (the
// sender does no CRC pass; see serve_native.cpp).  offset must be
// 64 KiB-aligned.  Returns 0, peer status, -1 socket, -2 protocol,
// -3 CRC mismatch.
int lz_read_part_bulk(int fd, uint64_t chunk_id, uint32_t version,
                      uint32_t part_id, uint32_t offset, uint32_t size,
                      uint8_t* out) {
    if (!send_read_request(fd, kTypeReadBulk, chunk_id, version, part_id,
                           offset, size))
        return -1;

    uint8_t header[8];
    if (!recv_all(fd, header, 8)) return -1;
    uint32_t type = get32(header);
    uint32_t length = get32(header + 4);
    if (type != kTypeReadBulkData) return -2;
    if (length < 1 + 4 + 8 + 1 + 4 + 4 + 4) return -2;
    uint8_t fixed[22];
    if (!recv_all(fd, fixed, sizeof(fixed))) return -1;
    if (fixed[0] != kProtoVersion) return -2;
    uint8_t status = fixed[13];
    uint32_t nblocks_expected =
        (offset + size - 1) / kBlockSize - offset / kBlockSize + 1;
    uint32_t ncrcs = get32(fixed + 18);
    if (status != 0) {
        // drain the (empty) remainder so the socket stays reusable
        uint32_t rest = length - 22;
        std::vector<uint8_t> sink(rest);
        if (rest && !recv_all(fd, sink.data(), rest)) return -1;
        return status;
    }
    if (ncrcs != nblocks_expected) return -2;
    std::vector<uint8_t> crcs(4 * ncrcs);
    if (!recv_all(fd, crcs.data(), crcs.size())) return -1;
    uint8_t dlen_raw[4];
    if (!recv_all(fd, dlen_raw, 4)) return -1;
    uint32_t dlen = get32(dlen_raw);
    if (dlen != size || length != 22 + 4 * ncrcs + 4 + dlen) return -2;
    if (!recv_all(fd, out, size)) return -1;
    // receiver-side integrity pass (the only CRC pass on this path)
    uint32_t end = offset + size;
    for (uint32_t b = 0; b < ncrcs; ++b) {
        uint32_t piece_start = offset + b * kBlockSize;
        uint32_t piece_end = std::min(end, piece_start + kBlockSize);
        if (lz_crc32(0, out + (piece_start - offset),
                     piece_end - piece_start) != get32(crcs.data() + 4 * b))
            return -3;
    }
    return 0;
}

// Bulk write: ONE CltocsWriteBulk frame (per-piece CRC table + raw
// range) and ONE WriteStatus ack for the whole range.  part_offset must
// be 64 KiB-aligned.  Assumes WriteInit was already exchanged.
int lz_write_part_bulk(int fd, uint64_t chunk_id, const uint8_t* payload,
                       uint64_t len, uint64_t part_offset,
                       uint32_t write_id) {
    if (part_offset % kBlockSize != 0 || len > (64u << 20)) return -2;
    std::vector<uint8_t> head;
    build_bulk_write_header(head, chunk_id, write_id, part_offset,
                            payload, len);
    if (!send_all(fd, head.data(), head.size())) return -1;
    if (!send_all(fd, payload, len)) return -1;
    // single ack
    uint8_t hdr[8];
    uint8_t pay[32];
    if (!recv_all(fd, hdr, 8)) return -1;
    uint32_t type = get32(hdr);
    uint32_t length = get32(hdr + 4);
    if (type != kTypeWriteStatus || length < 18 || length > sizeof(pay))
        return -2;
    if (!recv_all(fd, pay, length)) return -1;
    return parse_bulk_write_ack(pay, length, write_id);
}

// Stream [part_offset, part_offset+len) of payload as WriteData pieces
// (block-bounded, CRC per piece) and collect one ack per piece.
// Assumes WriteInit has already been exchanged on this socket.
int lz_write_part(int fd, uint64_t chunk_id, const uint8_t* payload,
                  uint64_t len, uint64_t part_offset,
                  uint32_t first_write_id) {
    std::vector<uint8_t> frame(8 + 1 + 4 + 8 + 4 + 4 + 4 + 4 + 4 + kBlockSize);
    uint32_t write_id = first_write_id;
    uint32_t pieces = 0;
    uint64_t pos = 0;
    while (pos < len) {
        uint64_t abs = part_offset + pos;
        uint32_t block = static_cast<uint32_t>(abs / kBlockSize);
        uint32_t block_off = static_cast<uint32_t>(abs % kBlockSize);
        uint32_t take = kBlockSize - block_off;
        if (take > len - pos) take = static_cast<uint32_t>(len - pos);
        const uint8_t* data = payload + pos;
        uint32_t crc = lz_crc32(0, data, take);
        size_t body = 1 + 4 + 8 + 4 + 4 + 4 + 4 + 4 + take;
        uint8_t* f = frame.data();
        put32(f, kTypeWriteData);
        put32(f + 4, static_cast<uint32_t>(body));
        f[8] = kProtoVersion;
        put32(f + 9, write_id);       // req_id
        put64(f + 13, chunk_id);
        put32(f + 21, write_id);
        put32(f + 25, block);
        put32(f + 29, block_off);
        put32(f + 33, crc);
        put32(f + 37, take);
        std::memcpy(f + 41, data, take);
        if (!send_all(fd, f, 8 + body)) return -1;
        ++write_id;
        ++pieces;
        pos += take;
    }
    // collect acks (they may interleave arbitrarily by write_id)
    std::vector<uint8_t> payload_buf(256);
    for (uint32_t i = 0; i < pieces; ++i) {
        uint8_t header[8];
        if (!recv_all(fd, header, 8)) return -1;
        uint32_t type = get32(header);
        uint32_t length = get32(header + 4);
        if (length < 1 || length > payload_buf.size()) return -2;
        if (!recv_all(fd, payload_buf.data(), length)) return -1;
        if (type != kTypeWriteStatus) return -2;
        if (length < 18 || payload_buf[0] != kProtoVersion) return -2;
        uint8_t status = payload_buf[17];
        if (status != 0) return status;
    }
    return 0;
}

// --- multi-part bulk reads: one poll loop over n sockets --------------------
//
// Two entry points, lz_read_parts_gather (a healthy region, de-interleaved
// as it lands) and lz_read_parts_wave (one wave of a read plan: any
// parts, each to its own place), share ONE receive state machine,
// read_bulk_parts: they differ in where a block lands and in what a
// failed part means for the others.
struct lz_part_req {
    int fd;
    uint64_t chunk_id;
    uint32_t version;
    uint32_t part_id;
    int32_t rc;
};

namespace {

// lz_part_req.rc of a part whose exchange is under way
constexpr int32_t kInFlight = 1 << 30;
// ... of a part not finished when the call's deadline came
constexpr int32_t kRcDeadline = -4;

// Where one part's bulk reply lands: block b of the reply goes to
// base + b * stride (stride == kBlockSize: the reply is contiguous).
struct BulkLanding {
    uint32_t offset;   // part-local, 64 KiB aligned
    uint32_t size;     // bytes asked for; the last block may be short
    uint8_t* base;
    uint64_t stride;
};

// A part's rc is read by the caller's other threads while the call
// runs (core/read_executor.py harvests finished parts at a wave's
// timeout): the bytes and their CRC checks are published before it.
inline void set_rc(lz_part_req& part, int32_t rc) {
    __atomic_store_n(&part.rc, rc, __ATOMIC_RELEASE);
}

// The receive state machine of the bulk read exchange (CltocsReadBulk
// 1206 out, one CstoclReadBulkData 1207 back: CRC table + raw range,
// verified HERE, the only CRC pass on this path), for n parts over n
// connected sockets in one poll loop. Entries whose rc is kInFlight on
// entry are read; the others are left alone.
//
// independent: each part runs to its own end whatever the others do,
// and one not finished at the deadline reads kRcDeadline. Otherwise
// the round ends at the first failed part (the caller reads the region
// again another way, so draining the rest would only burn bandwidth)
// and whatever is unfinished reads -1.
//
// parts[i].rc: 0 ok; >0 peer status; -1 socket; -2 protocol; -3 CRC.
// done_us[i] (may be null): microseconds from the start of the call to
// part i's end, on the steady clock. Returns 0 iff every part read is
// OK. A socket whose part did not end with rc 0 may hold an unread
// remainder: discard it, never pool it.
int read_bulk_parts(lz_part_req* parts, uint32_t n, const BulkLanding* land,
                    int64_t deadline_ms, bool independent,
                    uint64_t* done_us) {
    struct St {
        enum Phase { kHdr, kFixed, kCrcs, kDlen, kData } phase = kHdr;
        uint8_t small[32];
        uint32_t got = 0;          // bytes received in current phase
        uint32_t ncrcs = 0;
        std::vector<uint8_t> crcs;
        uint64_t received = 0;     // data bytes so far
    };
    const int64_t t0 = steady_us();
    std::vector<St> st(n);
    uint32_t live = 0;
    bool failed = false;
    auto finish = [&](uint32_t i, int32_t rc) {
        if (done_us) done_us[i] = static_cast<uint64_t>(steady_us() - t0);
        if (rc != 0) failed = true;
        set_rc(parts[i], rc);
        --live;
    };
    for (uint32_t i = 0; i < n; ++i) {
        if (parts[i].rc != kInFlight) continue;
        ++live;
        if (land[i].offset % kBlockSize || land[i].size == 0)
            finish(i, -2);
        else if (!send_read_request(parts[i].fd, kTypeReadBulk,
                                    parts[i].chunk_id, parts[i].version,
                                    parts[i].part_id, land[i].offset,
                                    land[i].size))
            finish(i, -1);
    }
    // the next destination of part i's data and how much may land there
    // in one recv: up to the block's end, or the reply's where the
    // landing is contiguous
    auto data_span = [&](uint32_t i, uint8_t*& dst) -> uint64_t {
        const uint64_t pos = st[i].received;
        const uint64_t in_blk = pos % kBlockSize;
        dst = land[i].base + (pos / kBlockSize) * land[i].stride + in_blk;
        const uint64_t left = land[i].size - pos;
        return land[i].stride == kBlockSize
                   ? left
                   : std::min<uint64_t>(kBlockSize - in_blk, left);
    };
    std::vector<pollfd> pfds(n);
    std::vector<uint32_t> part_of(n);
    while (live && (independent || !failed)) {
        const int64_t now = steady_ms();
        if (now >= deadline_ms) break;
        int nfds = 0;
        for (uint32_t i = 0; i < n; ++i) {
            if (parts[i].rc != kInFlight) continue;
            pfds[nfds].fd = parts[i].fd;
            pfds[nfds].events = POLLIN;
            pfds[nfds].revents = 0;
            part_of[nfds++] = i;
        }
        int pr = ::poll(pfds.data(), nfds,
                        static_cast<int>(std::min<int64_t>(
                            deadline_ms - now, 30000)));
        if (pr < 0) {
            if (errno == EINTR) continue;
            break;
        }
        for (int pi = 0; pi < nfds; ++pi) {
            if (!(pfds[pi].revents & (POLLIN | POLLERR | POLLHUP))) continue;
            const uint32_t i = part_of[pi];
            St& s = st[i];
            // drain as much as available without blocking
            while (parts[i].rc == kInFlight) {
                uint8_t* dst = s.small;
                uint64_t want = 0;
                switch (s.phase) {
                    case St::kHdr: want = 8; break;
                    case St::kFixed: want = 22; break;
                    case St::kCrcs:
                        dst = s.crcs.data();
                        want = s.crcs.size();
                        break;
                    case St::kDlen: want = 4; break;
                    case St::kData: want = data_span(i, dst); break;
                }
                ssize_t r = ::recv(parts[i].fd, dst + s.got,
                                   static_cast<size_t>(want - s.got),
                                   MSG_DONTWAIT);
                if (r == 0) { finish(i, -1); break; }
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) break;
                    if (errno == EINTR) continue;
                    finish(i, -1);
                    break;
                }
                s.got += static_cast<uint32_t>(r);
                if (s.got < want) continue;
                s.got = 0;
                switch (s.phase) {
                    case St::kHdr:
                        if (get32(s.small) != kTypeReadBulkData ||
                            get32(s.small + 4) < 22 + 4)
                            finish(i, -2);
                        else
                            s.phase = St::kFixed;
                        break;
                    case St::kFixed:
                        s.ncrcs = get32(s.small + 18);
                        if (s.small[0] != kProtoVersion) {
                            finish(i, -2);
                        } else if (s.small[13] != 0) {
                            // a refusal: what is left of its frame
                            // stays unread (the socket is discarded)
                            finish(i, s.small[13]);
                        } else if (s.ncrcs != (land[i].size + kBlockSize -
                                               1) / kBlockSize) {
                            finish(i, -2);
                        } else {
                            s.crcs.resize(4 * size_t(s.ncrcs));
                            s.phase = St::kCrcs;
                        }
                        break;
                    case St::kCrcs:
                        s.phase = St::kDlen;
                        break;
                    case St::kDlen:
                        if (get32(s.small) != land[i].size)
                            finish(i, -2);
                        else
                            s.phase = St::kData;
                        break;
                    case St::kData: {
                        s.received += want;
                        if (s.received < land[i].size) break;
                        // every block's CRC, over where it landed
                        int32_t rc = 0;
                        for (uint32_t b = 0; b < s.ncrcs && rc == 0; ++b) {
                            const uint32_t len = std::min<uint32_t>(
                                kBlockSize, land[i].size - b * kBlockSize);
                            if (lz_crc32(0, land[i].base + b * land[i].stride,
                                         len) != get32(s.crcs.data() + 4 * b))
                                rc = -3;
                        }
                        finish(i, rc);
                        break;
                    }
                }
            }
        }
    }
    for (uint32_t i = 0; i < n; ++i) {
        if (parts[i].rc == kInFlight) {
            const bool late = independent && steady_ms() >= deadline_ms;
            finish(i, late ? kRcDeadline : -1);
        }
    }
    return failed ? -1 : 0;
}

}  // namespace

// Whole-stripe fan-in: read the SAME [offset, offset+size) range of d
// data parts over d already-connected sockets in ONE poll-driven loop,
// scattering bytes straight into their gathered (de-interleaved) chunk
// positions: part i's block j lands at out + (j*d + i)*64Ki.  One
// native call replaces d thread dispatches + d Python wrappers + a
// separate gather pass — on a small-core host the per-exchange overhead
// was the EC read path's dominant cost.
//
// parts[i].rc: 0 ok; >0 peer status; -1 socket; -2 protocol; -3 CRC.
// Returns 0 when every part succeeded, -1 otherwise (caller falls back
// to the wave executor for recovery).  offset (the part-local byte
// offset, identical across parts) must be 64 KiB aligned;
// region_blocks is the number of 64 KiB chunk blocks to produce, and
// out must cover region_blocks * 64 KiB bytes.
int lz_read_parts_gather(lz_part_req* parts, uint32_t d, uint32_t offset,
                         uint32_t region_blocks, uint8_t* out,
                         uint32_t max_ms) {
    if (offset % kBlockSize || d == 0 || region_blocks == 0) return -1;
    // part i serves region blocks {j*d+i < region_blocks}: its request
    // size is its own block count (parts differ when d doesn't divide
    // the region)
    std::vector<BulkLanding> land(d);
    for (uint32_t i = 0; i < d; ++i) {
        const uint32_t blocks =
            (region_blocks > i) ? (region_blocks - i + d - 1) / d : 0;
        land[i] = {offset, blocks * kBlockSize,
                   out + uint64_t(i) * kBlockSize, uint64_t(d) * kBlockSize};
        parts[i].rc = blocks ? kInFlight : 0;
    }
    return read_bulk_parts(parts, d, land.data(), steady_ms() + max_ms,
                           false, nullptr);
}

// One wave of a read plan: n parts over n already-connected sockets in
// ONE poll loop on one thread, part i's [offsets[i], +sizes[i]) landing
// contiguous at dsts[i] (its place in the plan's buffer). The parts are
// independent: one that fails leaves the others running, since any k
// of them can serve the plan. Entries whose rc the caller set to
// LZ_WAVE_PENDING (1 << 30) are read, the others left alone (a part
// the caller's pool had no idle socket for: it reads another way).
//
// parts[i].rc: 0 ok (every block's CRC checked); >0 peer status; -1
// socket; -2 protocol (or a misaligned offset, a size of 0); -3 CRC;
// -4 not finished at the deadline (max_ms from the call's start). An rc
// is stored with release order after the part's last byte and check, so
// another thread may read it while the call runs. done_us[i] receives
// the microseconds from the call's start to part i's end on the steady
// clock. Returns 0 iff every pending part ended OK, else -1.
int lz_read_parts_wave(lz_part_req* parts, uint32_t n,
                       const uint32_t* offsets, const uint32_t* sizes,
                       uint8_t* const* dsts, uint32_t max_ms,
                       uint64_t* done_us) {
    std::vector<BulkLanding> land(n);
    for (uint32_t i = 0; i < n; ++i)
        land[i] = {offsets[i], sizes[i], dsts[i], kBlockSize};
    return read_bulk_parts(parts, n, land.data(), steady_ms() + max_ms,
                           true, done_us);
}

namespace {

// What one socket sends in a status round: a frame head and the payload
// that follows it on the wire (none for a handshake frame).
struct RoundSend {
    const uint8_t* head = nullptr;
    uint64_t head_len = 0;
    const uint8_t* pay = nullptr;
    uint64_t pay_len = 0;
};

// One poll-driven round over n connected sockets: socket i sends
// out[i] (head, then payload) and reads ONE CstoclWriteStatus back.
// The shared body of the three legs of a part exchange (WriteInit,
// bulk data, WriteEnd). Entries whose rc
// the caller has already set nonzero are left alone and fail the round
// before a byte is sent. match_write_id: the status must echo
// parts[i].version (a bulk ack); a handshake's status carries no id.
//
// parts[i].rc: 0 ok; >0 peer status; -1 socket (or deadline); -2
// protocol. Returns 0 iff every part's status arrived and is OK; the
// round ends on the first failure (the others read -1).
int status_round(lz_part_req* parts, uint32_t n, const RoundSend* out,
                 bool match_write_id, int64_t deadline) {
    struct St {
        enum Phase { kSendHdr, kSendPay, kAckHdr, kAckPay, kDone };
        Phase phase = kSendHdr;
        uint64_t sent = 0;   // bytes sent in the current phase
        uint32_t got = 0;    // bytes received in the current phase
        uint32_t ack_len = 0;
        uint8_t small[32];
    };
    std::vector<St> st(n);
    uint32_t live = 0;
    bool failed = false;
    for (uint32_t i = 0; i < n; ++i) {
        if (parts[i].rc != 0) { failed = true; continue; }
        parts[i].rc = kInFlight;
        ++live;
    }
    std::vector<pollfd> pfds(n);
    while (live && !failed) {
        const int64_t now = steady_ms();
        if (now >= deadline) break;
        int nfds = 0;
        for (uint32_t i = 0; i < n; ++i) {
            if (parts[i].rc != kInFlight) continue;
            pfds[nfds].fd = parts[i].fd;
            pfds[nfds].events =
                (st[i].phase <= St::kSendPay) ? POLLOUT : POLLIN;
            pfds[nfds].revents = 0;
            ++nfds;
        }
        int pr = ::poll(pfds.data(), nfds,
                        static_cast<int>(std::min<int64_t>(deadline - now,
                                                           30000)));
        if (pr < 0) {
            if (errno == EINTR) continue;
            break;
        }
        for (int pi = 0; pi < nfds; ++pi) {
            if (!(pfds[pi].revents &
                  (POLLIN | POLLOUT | POLLERR | POLLHUP)))
                continue;
            uint32_t i = 0;
            while (i < n && parts[i].fd != pfds[pi].fd) ++i;
            if (i == n) continue;
            St& s = st[i];
            bool progress = true;
            while (progress && parts[i].rc == kInFlight) {
                progress = false;
                if (s.phase == St::kSendHdr || s.phase == St::kSendPay) {
                    const bool hdr = s.phase == St::kSendHdr;
                    const uint8_t* src = hdr ? out[i].head : out[i].pay;
                    const uint64_t total =
                        hdr ? out[i].head_len : out[i].pay_len;
                    while (s.sent < total) {
                        ssize_t w = ::send(parts[i].fd, src + s.sent,
                                           static_cast<size_t>(
                                               total - s.sent),
                                           MSG_DONTWAIT | MSG_NOSIGNAL);
                        if (w < 0) {
                            if (errno == EAGAIN || errno == EWOULDBLOCK)
                                break;
                            if (errno == EINTR) continue;
                            parts[i].rc = -1; --live;
                            break;
                        }
                        s.sent += static_cast<uint64_t>(w);
                    }
                    if (parts[i].rc != kInFlight) break;
                    if (s.sent >= total) {
                        s.sent = 0;
                        s.phase = hdr ? St::kSendPay : St::kAckHdr;
                        progress = true;
                    }
                    continue;
                }
                // status phases: the 8-byte frame header, then its body
                const uint32_t want =
                    (s.phase == St::kAckHdr) ? 8 : s.ack_len;
                ssize_t r = ::recv(parts[i].fd, s.small + s.got,
                                   want - s.got, MSG_DONTWAIT);
                if (r == 0) { parts[i].rc = -1; --live; break; }
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) break;
                    if (errno == EINTR) { progress = true; continue; }
                    parts[i].rc = -1; --live;
                    break;
                }
                s.got += static_cast<uint32_t>(r);
                if (s.got < want) { progress = true; continue; }
                s.got = 0;
                if (s.phase == St::kAckHdr) {
                    const uint32_t type = get32(s.small);
                    s.ack_len = get32(s.small + 4);
                    if (type != kTypeWriteStatus || s.ack_len < 18 ||
                        s.ack_len > sizeof(s.small)) {
                        parts[i].rc = -2; --live;
                        break;
                    }
                    s.phase = St::kAckPay;
                    progress = true;
                } else {
                    parts[i].rc = match_write_id
                        ? parse_bulk_write_ack(s.small, s.ack_len,
                                               parts[i].version)
                        : parse_write_status(s.small, s.ack_len);
                    s.phase = St::kDone;
                    --live;
                }
            }
        }
        for (uint32_t i = 0; i < n; ++i) {
            if (parts[i].rc != 0 && parts[i].rc != kInFlight) {
                failed = true;
                break;
            }
        }
    }
    int ret = 0;
    for (uint32_t i = 0; i < n; ++i) {
        if (parts[i].rc == kInFlight) parts[i].rc = -1;
        if (parts[i].rc != 0) ret = -1;
    }
    return ret;
}

// The bulk leg: one 1214 frame (per-block CRCs computed here, GIL-free)
// + one ack per part, all n in ONE poll-driven loop (the mirror of
// lz_read_parts_gather for the write path). parts[i].version carries
// the bulk write_id for part i (reusing the request struct; the chunk
// version is already bound by WriteInit).
int scatter_bulk(lz_part_req* parts, uint32_t n,
                 const uint8_t* const* payloads, const uint64_t* lens,
                 uint64_t part_offset, int64_t deadline) {
    if (n == 0 || part_offset % kBlockSize != 0) return -1;
    std::vector<std::vector<uint8_t>> heads(n);
    std::vector<RoundSend> out(n);
    for (uint32_t i = 0; i < n; ++i) {
        parts[i].rc = 0;
        if (lens[i] > (64u << 20)) { parts[i].rc = -2; continue; }
        build_bulk_write_header(heads[i], parts[i].chunk_id,
                                parts[i].version, part_offset,
                                payloads[i], lens[i]);
        out[i].head = heads[i].data();
        out[i].head_len = heads[i].size();
        out[i].pay = payloads[i];
        out[i].pay_len = lens[i];
    }
    return status_round(parts, n, out.data(), true, deadline);
}

// A handshake leg: one caller-built frame per socket, one status each.
int handshake_round(lz_part_req* parts, uint32_t n,
                    const uint8_t* const* frames, const uint32_t* lens,
                    int64_t deadline) {
    std::vector<RoundSend> out(n);
    for (uint32_t i = 0; i < n; ++i) {
        parts[i].rc = 0;
        out[i].head = frames[i];
        out[i].head_len = lens[i];
    }
    return status_round(parts, n, out.data(), false, deadline);
}

}  // namespace

// The whole one-shot part exchange in one call: over n connected
// sockets, three status rounds with no caller code between them:
//   leg 1  one caller-built WriteInit frame per socket, one status each;
//   leg 2  one bulk frame per socket, one ack each (scatter_bulk);
//   leg 3  one caller-built WriteEnd frame per socket, one status each.
// A leg starts only when every status of the one before is in and OK:
// no data goes to a server that has not accepted the init, and no End
// is left unread for the socket's next user. The frames are the
// caller's bytes (proto/messages.py stays the one source of the wire
// format; the trace and session ids ride the init as the caller
// encoded them); only the status replies are parsed here.
//
// One deadline (max_ms) covers the call. leg_us[0..2] receive each
// leg's duration in microseconds on the steady clock (0 for a leg that
// never started). Returns 0 iff every part passed every leg, else the
// leg that failed (1, 2, 3) with parts[i].rc as status_round sets it
// (0 ok; >0 peer status; -1 socket; -2 protocol); bad arguments fail
// leg 2 (whose precondition they break) with every rc -2, before a
// byte is sent.
int lz_write_parts_exchange(lz_part_req* parts, uint32_t n,
                            const uint8_t* const* init_frames,
                            const uint32_t* init_lens,
                            const uint8_t* const* payloads,
                            const uint64_t* lens, uint64_t part_offset,
                            const uint8_t* const* end_frames,
                            const uint32_t* end_lens, uint32_t max_ms,
                            uint64_t* leg_us) {
    leg_us[0] = leg_us[1] = leg_us[2] = 0;
    if (n == 0 || part_offset % kBlockSize != 0) {
        for (uint32_t i = 0; i < n; ++i) parts[i].rc = -2;
        return 2;
    }
    const int64_t deadline = steady_ms() + max_ms;
    int64_t t0 = steady_us();
    for (int leg = 1; leg <= 3; ++leg) {
        const int rc =
            leg == 1 ? handshake_round(parts, n, init_frames, init_lens,
                                       deadline)
            : leg == 2 ? scatter_bulk(parts, n, payloads, lens,
                                      part_offset, deadline)
                       : handshake_round(parts, n, end_frames, end_lens,
                                         deadline);
        const int64_t t1 = steady_us();
        leg_us[leg - 1] = static_cast<uint64_t>(t1 - t0);
        t0 = t1;
        if (rc != 0) return leg;
    }
    return 0;
}

// --- windowed / vectored scatter writes ------------------------------------
//
// lz_write_parts_scatterv sends one segment of a chunk's parts over
// connections a session keeps open (PartsScatterSession: one handshake
// pair a chunk, many segments): frames are part-addressed (type 1215),
// so several parts of one chunk can multiplex ONE connection to their
// shared chunkserver; header + payload leave through a single
// scatter-gather sendmsg per socket pass (no separate header syscall,
// no payload staging copy); and with kScatterNoAck the call returns as
// soon as every byte is handed to the kernel — the acks are collected
// later by lz_write_collect_acks, so the caller can keep an N-deep
// window of unacknowledged segments in flight instead of paying one
// ack round trip per segment (the stripe-serial round trips PR 1's
// phase telemetry blamed the send phase for).
//
// parts[i].version carries the bulk write_id (as on the 1214 path);
// parts[i].part_id addresses the part inside the frame. Entries MAY
// share fds; per fd they are sent — and acknowledged — in entry order.

constexpr uint32_t kScatterNoAck = 1;

namespace {

// Collect one CstoclWriteStatus per entry, entries on the same fd in
// order. parts[i].version = the expected write_id. Fills parts[i].rc;
// returns 0 iff every entry acked OK.
int collect_acks_inner(lz_part_req* parts, uint32_t n, int64_t deadline) {
    struct AckQ {
        int fd;
        std::vector<uint32_t> entries;
        size_t cur = 0;
        int phase = 0;  // 0: frame header, 1: ack payload
        uint32_t got = 0;
        uint32_t ack_len = 0;
        uint8_t small[32];
    };
    std::vector<AckQ> qs;
    for (uint32_t i = 0; i < n; ++i) {
        parts[i].rc = 1 << 30;
        AckQ* q = nullptr;
        for (auto& cand : qs)
            if (cand.fd == parts[i].fd) { q = &cand; break; }
        if (q == nullptr) {
            qs.emplace_back();
            q = &qs.back();
            q->fd = parts[i].fd;
        }
        q->entries.push_back(i);
    }
    uint32_t live = n;
    bool failed = false;
    std::vector<pollfd> pfds(qs.size());
    while (live && !failed) {
        const int64_t now = steady_ms();
        if (now >= deadline) break;
        int nfds = 0;
        for (auto& q : qs) {
            if (q.cur >= q.entries.size()) continue;
            pfds[nfds].fd = q.fd;
            pfds[nfds].events = POLLIN;
            pfds[nfds].revents = 0;
            ++nfds;
        }
        int pr = ::poll(pfds.data(), nfds,
                        static_cast<int>(std::min<int64_t>(deadline - now,
                                                           30000)));
        if (pr < 0) {
            if (errno == EINTR) continue;
            break;
        }
        for (int pi = 0; pi < nfds; ++pi) {
            if (!(pfds[pi].revents & (POLLIN | POLLERR | POLLHUP))) continue;
            AckQ* q = nullptr;
            for (auto& cand : qs)
                if (cand.fd == pfds[pi].fd && cand.cur < cand.entries.size()) {
                    q = &cand;
                    break;
                }
            if (q == nullptr) continue;
            bool progress = true;
            while (progress && q->cur < q->entries.size()) {
                progress = false;
                const uint32_t idx = q->entries[q->cur];
                const uint32_t want = q->phase == 0 ? 8 : q->ack_len;
                ssize_t r = ::recv(q->fd, q->small + q->got, want - q->got,
                                   MSG_DONTWAIT);
                if (r == 0) {
                    parts[idx].rc = -1; --live; failed = true; break;
                }
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) break;
                    if (errno == EINTR) { progress = true; continue; }
                    parts[idx].rc = -1; --live; failed = true; break;
                }
                q->got += static_cast<uint32_t>(r);
                if (q->got < want) { progress = true; continue; }
                q->got = 0;
                if (q->phase == 0) {
                    const uint32_t type = get32(q->small);
                    q->ack_len = get32(q->small + 4);
                    if (type != kTypeWriteStatus || q->ack_len < 18 ||
                        q->ack_len > sizeof(q->small)) {
                        parts[idx].rc = -2; --live; failed = true; break;
                    }
                    q->phase = 1;
                    progress = true;
                } else {
                    const int rc = parse_bulk_write_ack(
                        q->small, q->ack_len, parts[idx].version);
                    parts[idx].rc = rc;
                    --live;
                    if (rc != 0) { failed = true; break; }
                    q->phase = 0;
                    ++q->cur;
                    progress = true;
                }
            }
        }
    }
    int ret = 0;
    for (uint32_t i = 0; i < n; ++i) {
        if (parts[i].rc == (1 << 30)) parts[i].rc = -1;
        if (parts[i].rc != 0) ret = -1;
    }
    return ret;
}

}  // namespace

// Vectored multi-part bulk write. flags: kScatterNoAck skips the ack
// phase (collect later with lz_write_collect_acks). Returns 0 iff
// every entry succeeded; per-entry codes land in parts[i].rc.
int lz_write_parts_scatterv(lz_part_req* parts, uint32_t n,
                            const uint8_t* const* payloads,
                            const uint64_t* lens, uint64_t part_offset,
                            uint32_t max_ms, uint32_t flags) {
    if (n == 0 || part_offset % kBlockSize != 0) return -1;
    std::vector<std::vector<uint8_t>> heads(n);
    bool bad = false;
    for (uint32_t i = 0; i < n; ++i) {
        if (lens[i] > (64u << 20)) {
            parts[i].rc = -2;
            bad = true;
            continue;
        }
        build_bulk_write_part_header(heads[i], parts[i].chunk_id,
                                     parts[i].version, parts[i].part_id,
                                     part_offset, payloads[i], lens[i]);
        parts[i].rc = 1 << 30;
    }
    if (bad) {
        for (uint32_t i = 0; i < n; ++i)
            if (parts[i].rc == (1 << 30)) parts[i].rc = -1;
        return -1;
    }
    // per-fd send queues: entries sharing a connection go out strictly
    // in entry order, each as [header | payload] iovec pairs
    struct SendQ {
        int fd;
        std::vector<uint32_t> entries;
        size_t cur = 0;      // entry being sent
        uint64_t done = 0;   // bytes of the current entry already sent
        bool dead = false;
    };
    std::vector<SendQ> qs;
    for (uint32_t i = 0; i < n; ++i) {
        SendQ* q = nullptr;
        for (auto& cand : qs)
            if (cand.fd == parts[i].fd) { q = &cand; break; }
        if (q == nullptr) {
            qs.emplace_back();
            q = &qs.back();
            q->fd = parts[i].fd;
        }
        q->entries.push_back(i);
    }
    const int64_t deadline = steady_ms() + max_ms;
    bool failed = false;
    std::vector<pollfd> pfds(qs.size());
    auto queue_unfinished = [&](const SendQ& q) {
        return !q.dead && q.cur < q.entries.size();
    };
    for (;;) {
        int pending = 0;
        for (auto& q : qs)
            if (queue_unfinished(q)) ++pending;
        if (pending == 0 || failed) break;
        const int64_t now = steady_ms();
        if (now >= deadline) {
            failed = true;
            break;
        }
        int nfds = 0;
        for (auto& q : qs) {
            if (!queue_unfinished(q)) continue;
            pfds[nfds].fd = q.fd;
            pfds[nfds].events = POLLOUT;
            pfds[nfds].revents = 0;
            ++nfds;
        }
        int pr = ::poll(pfds.data(), nfds,
                        static_cast<int>(std::min<int64_t>(deadline - now,
                                                           30000)));
        if (pr < 0) {
            if (errno == EINTR) continue;
            failed = true;
            break;
        }
        for (int pi = 0; pi < nfds; ++pi) {
            if (!(pfds[pi].revents & (POLLOUT | POLLERR | POLLHUP))) continue;
            SendQ* q = nullptr;
            for (auto& cand : qs)
                if (cand.fd == pfds[pi].fd && queue_unfinished(cand)) {
                    q = &cand;
                    break;
                }
            if (q == nullptr) continue;
            bool progress = true;
            while (progress && queue_unfinished(*q)) {
                progress = false;
                // gather up to 16 iovecs starting at (cur, done):
                // remaining header slice + payload slice of the current
                // entry, then whole header/payload pairs of successors
                struct iovec iov[16];
                int niov = 0;
                uint64_t pos = q->done;
                for (size_t e = q->cur;
                     e < q->entries.size() && niov < 15; ++e) {
                    const uint32_t idx = q->entries[e];
                    const uint64_t hlen = heads[idx].size();
                    if (pos < hlen) {
                        iov[niov].iov_base = heads[idx].data() + pos;
                        iov[niov].iov_len = static_cast<size_t>(hlen - pos);
                        ++niov;
                        if (lens[idx] > 0) {
                            iov[niov].iov_base = const_cast<uint8_t*>(
                                payloads[idx]);
                            iov[niov].iov_len =
                                static_cast<size_t>(lens[idx]);
                            ++niov;
                        }
                    } else if (pos < hlen + lens[idx]) {
                        iov[niov].iov_base = const_cast<uint8_t*>(
                            payloads[idx] + (pos - hlen));
                        iov[niov].iov_len =
                            static_cast<size_t>(hlen + lens[idx] - pos);
                        ++niov;
                    }
                    pos = 0;
                }
                struct msghdr mh {};
                mh.msg_iov = iov;
                mh.msg_iovlen = static_cast<size_t>(niov);
                ssize_t w = ::sendmsg(q->fd, &mh,
                                      MSG_DONTWAIT | MSG_NOSIGNAL);
                if (w < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) break;
                    if (errno == EINTR) { progress = true; continue; }
                    for (size_t e = q->cur; e < q->entries.size(); ++e)
                        parts[q->entries[e]].rc = -1;
                    q->dead = true;
                    failed = true;
                    break;
                }
                uint64_t sent = static_cast<uint64_t>(w);
                q->done += sent;
                while (q->cur < q->entries.size()) {
                    const uint32_t idx = q->entries[q->cur];
                    const uint64_t total = heads[idx].size() + lens[idx];
                    if (q->done < total) break;
                    q->done -= total;
                    if (flags & kScatterNoAck) parts[idx].rc = 0;
                    ++q->cur;
                }
                progress = sent > 0;
            }
        }
    }
    if (failed) {
        for (uint32_t i = 0; i < n; ++i)
            if (parts[i].rc == (1 << 30)) parts[i].rc = -1;
        return -1;
    }
    if (flags & kScatterNoAck) {
        for (uint32_t i = 0; i < n; ++i)
            if (parts[i].rc == (1 << 30)) parts[i].rc = 0;
        return 0;
    }
    return collect_acks_inner(parts, n, deadline);
}

// Collect the acks of previously sent (kScatterNoAck) bulk frames:
// parts[i].fd + parts[i].version (= expected write_id), entries on the
// same fd acknowledged in entry order. Returns 0 iff all acked OK.
int lz_write_collect_acks(lz_part_req* parts, uint32_t n, uint32_t max_ms) {
    if (n == 0) return 0;
    return collect_acks_inner(parts, n, steady_ms() + max_ms);
}

// --- shared-memory ring sends ----------------------------------------------
//
// The destination regions sit in the connection's negotiated memfd
// ring segment (shm_ring.h): `dsts[i]` points at the CLIENT's mapping
// of entry i's staged region, `ring_offs[i]` is the same region's
// offset inside the segment (what the server's mapping indexes).
// `srcs[i]` is where the payload bytes currently live: when it differs
// from `dsts[i]` (data rows staged outside the ring) this call moves
// them with ONE GIL-free memcpy — the only copy left on the path;
// parity rows are encoded straight into the arena, so src == dst and
// no byte moves at all.  Then the per-64KiB piece CRC pass runs over
// the mapped memory and one tiny CltocsShmWritePart descriptor frame
// per entry ships, all of one fd's frames concatenated into a single
// send.  Acks are ordinary CstoclWriteStatus frames: with kScatterNoAck
// they are collected later by lz_write_collect_acks, exactly like the
// 1215 scatterv path, so ring and socket-copy segments can interleave
// on one connection.
//
// parts[i].version carries the bulk write_id; parts[i].part_id the
// target part.  Returns 0 iff every entry was handed off (and, without
// kScatterNoAck, acked OK); per-entry codes land in parts[i].rc.
int lz_shm_write_descs(lz_part_req* parts, uint32_t n,
                       const uint8_t* const* srcs,
                       const uint8_t* const* dsts,
                       const uint64_t* lens, const uint64_t* ring_offs,
                       uint64_t part_offset, uint32_t max_ms,
                       uint32_t flags) {
    if (n == 0 || part_offset % kBlockSize != 0) return -1;
    const int64_t deadline = steady_ms() + max_ms;
    // per-fd send buffers, entries in order (ack order == entry order)
    struct SendBuf {
        int fd;
        std::vector<uint8_t> bytes;
    };
    std::vector<SendBuf> bufs;
    std::vector<uint32_t> crcs;
    std::vector<uint8_t> frame;
    bool bad = false;
    for (uint32_t i = 0; i < n; ++i) {
        if (lens[i] == 0 || lens[i] > (64u << 20)) {
            parts[i].rc = -2;
            bad = true;
            continue;
        }
        if (srcs[i] != dsts[i])
            std::memcpy(const_cast<uint8_t*>(dsts[i]), srcs[i],
                        static_cast<size_t>(lens[i]));
        const uint32_t ncrcs =
            static_cast<uint32_t>((lens[i] + kBlockSize - 1) / kBlockSize);
        crcs.resize(ncrcs);
        for (uint32_t b = 0; b < ncrcs; ++b) {
            const uint64_t start = uint64_t(b) * kBlockSize;
            const uint32_t piece = static_cast<uint32_t>(
                std::min<uint64_t>(kBlockSize, lens[i] - start));
            crcs[b] = lz_crc32(0, dsts[i] + start, piece);
        }
        lzshm::build_shm_desc_frame(
            frame, parts[i].chunk_id, parts[i].version, parts[i].part_id,
            part_offset, ring_offs[i], static_cast<uint32_t>(lens[i]),
            crcs.data(), ncrcs);
        SendBuf* sb = nullptr;
        for (auto& cand : bufs)
            if (cand.fd == parts[i].fd) { sb = &cand; break; }
        if (sb == nullptr) {
            bufs.emplace_back();
            sb = &bufs.back();
            sb->fd = parts[i].fd;
        }
        sb->bytes.insert(sb->bytes.end(), frame.begin(), frame.end());
        parts[i].rc = 1 << 30;
    }
    if (bad) {
        for (uint32_t i = 0; i < n; ++i)
            if (parts[i].rc == (1 << 30)) parts[i].rc = -1;
        return -1;
    }
    // descriptors are tens of bytes each: one blocking send per fd
    // (client sockets carry SO_SNDTIMEO; a full buffer means the peer
    // is wedged and the timeout converts it to a socket error)
    for (auto& sb : bufs) {
        if (!send_all(sb.fd, sb.bytes.data(), sb.bytes.size())) {
            for (uint32_t i = 0; i < n; ++i)
                if (parts[i].fd == sb.fd && parts[i].rc == (1 << 30))
                    parts[i].rc = -1;
        }
    }
    bool failed = false;
    for (uint32_t i = 0; i < n; ++i)
        if (parts[i].rc != (1 << 30)) failed = true;
    if (failed) {
        for (uint32_t i = 0; i < n; ++i)
            if (parts[i].rc == (1 << 30)) parts[i].rc = -1;
        return -1;
    }
    if (flags & kScatterNoAck) {
        for (uint32_t i = 0; i < n; ++i) parts[i].rc = 0;
        return 0;
    }
    return collect_acks_inner(parts, n, deadline);
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Server side: serve one CltocsRead in two phases so the chunk-file
// lock never spans network IO.
//
//   lz_load_read   — pread every touched block, verify against the
//                    on-disk CRC table, scatter the requested range
//                    into a contiguous buffer + per-piece CRCs.
//                    Called with the chunk-file lock held.
//   lz_stream_read — frame and send CstoclReadData pieces + the final
//                    CstoclReadStatus on the asyncio socket (non-
//                    blocking: poll on EAGAIN). Called WITHOUT the
//                    lock; load errors are reported by the Python
//                    side through its own framing instead.
//
// On-disk layout (keep in sync with chunkserver/chunk_store.py):
// [1 KiB signature][4 KiB big-endian u32 CRC table][block data...].

namespace {

constexpr size_t kSignatureSize = 1024;
constexpr size_t kHeaderSize = kSignatureSize + 4 * 1024;
constexpr uint8_t kStatusOk = 0;
constexpr uint8_t kStatusCrcError = 20;
constexpr uint8_t kStatusEio = 9;

int64_t monotonic_ms() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000 + ts.tv_nsec / 1000000;
}

// asyncio sockets are non-blocking: wait for POLLOUT on EAGAIN, but
// never past deadline_ms — a trickle-draining client must not pin a
// serve thread forever (per-poll timeouts reset on every byte of
// progress; the absolute deadline does not).
bool send_all_poll(int fd, const uint8_t* buf, size_t len,
                   int64_t deadline_ms) {
    while (len) {
        ssize_t n = ::send(fd, buf, len, MSG_NOSIGNAL);
        if (n < 0) {
            if (errno == EINTR) continue;
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                int64_t left = deadline_ms - monotonic_ms();
                if (left <= 0) return false;
                struct pollfd pfd{fd, POLLOUT, 0};
                int pr = ::poll(&pfd, 1,
                                static_cast<int>(std::min<int64_t>(left, 30000)));
                if (pr < 0 && errno == EINTR) continue;
                if (pr < 0) return false;
                continue;  // pr==0: re-check the deadline
            }
            return false;
        }
        if (n == 0) return false;
        buf += n;
        len -= static_cast<size_t>(n);
    }
    return true;
}

uint32_t empty_block_crc() {
    static const uint32_t crc = [] {
        std::vector<uint8_t> zeros(kBlockSize, 0);
        return lz_crc32(0, zeros.data(), zeros.size());
    }();
    return crc;
}

bool pread_full(int fd, uint8_t* buf, size_t len, uint64_t off, size_t* got) {
    size_t done = 0;
    while (done < len) {
        ssize_t n = ::pread(fd, buf + done, len - done,
                            static_cast<off_t>(off + done));
        if (n < 0) {
            if (errno == EINTR) continue;
            return false;
        }
        if (n == 0) break;  // EOF: caller zero-pads
        done += static_cast<size_t>(n);
    }
    *got = done;
    return true;
}

}  // namespace

extern "C" {

// Phase 1: load + verify [offset, offset+size) of the part file into
// out_data (contiguous) and out_crcs (one u32 per touched block piece).
// Returns 0, or the protocol status byte to send (CRC_ERROR / EIO).
int lz_load_read(int file_fd, uint32_t offset, uint32_t size,
                 uint64_t data_len, uint8_t* out_data, uint32_t* out_crcs) {
    std::vector<uint8_t> block(kBlockSize);
    uint64_t pos = offset;
    const uint64_t end = static_cast<uint64_t>(offset) + size;

    // one pread covers every touched CRC slot (contiguous in the table)
    const uint64_t first_blk = offset / kBlockSize;
    const uint64_t last_blk = (end - 1) / kBlockSize;
    std::vector<uint8_t> slots(4 * (last_blk - first_blk + 1), 0);
    size_t sgot = 0;
    if (!pread_full(file_fd, slots.data(), slots.size(),
                    kSignatureSize + 4 * first_blk, &sgot) ||
        sgot < slots.size()) {
        // the CRC table always exists in a well-formed file; a short
        // read means header truncation — refuse rather than fabricate
        // sparse zero data with self-consistent CRCs
        return kStatusEio;
    }

    size_t piece_idx = 0;
    while (pos < end) {
        const uint64_t blk = pos / kBlockSize;
        const uint64_t block_start = blk * kBlockSize;
        const uint64_t piece_end =
            std::min<uint64_t>(end, block_start + kBlockSize);
        const size_t piece_len = static_cast<size_t>(piece_end - pos);

        size_t got = 0;
        if (!pread_full(file_fd, block.data(), kBlockSize,
                        kHeaderSize + block_start, &got)) {
            return kStatusEio;
        }
        if (got < kBlockSize)
            std::memset(block.data() + got, 0, kBlockSize - got);

        const uint32_t stored = get32(slots.data() + 4 * (blk - first_blk));

        uint32_t crc;
        if (block_start < data_len || stored != 0) {
            // inside the data region a zero slot means a sparse hole
            const uint32_t expected = stored ? stored : empty_block_crc();
            if (lz_crc32(0, block.data(), kBlockSize) != expected)
                return kStatusCrcError;
            crc = expected;
        } else {
            crc = empty_block_crc();
        }

        const size_t in_block = static_cast<size_t>(pos - block_start);
        if (piece_len != kBlockSize)
            crc = lz_crc32(0, block.data() + in_block, piece_len);
        std::memcpy(out_data + (pos - offset), block.data() + in_block,
                    piece_len);
        out_crcs[piece_idx++] = crc;
        pos = piece_end;
    }
    return 0;
}

// Phase 2: stream the loaded range as CstoclReadData frames + the final
// OK CstoclReadStatus. Returns 0, or -1 if the socket died.
int lz_stream_read(int sock_fd, uint64_t chunk_id, uint32_t req_id,
                   uint32_t offset, uint32_t size, const uint8_t* data,
                   const uint32_t* crcs, uint32_t max_ms) {
    const int64_t deadline = monotonic_ms() + max_ms;
    // frame = header + version + req_id + chunk_id + offset + crc
    //         + data(u32 len + bytes)
    constexpr size_t kPre = 8 + 1 + 4 + 8 + 4 + 4 + 4;
    std::vector<uint8_t> frame(kPre + kBlockSize);
    uint64_t pos = offset;
    const uint64_t end = static_cast<uint64_t>(offset) + size;
    size_t piece_idx = 0;
    while (pos < end) {
        const uint64_t block_start = (pos / kBlockSize) * kBlockSize;
        const uint64_t piece_end =
            std::min<uint64_t>(end, block_start + kBlockSize);
        const size_t piece_len = static_cast<size_t>(piece_end - pos);
        uint8_t* f = frame.data();
        put32(f, kTypeReadData);
        put32(f + 4, static_cast<uint32_t>(1 + 4 + 8 + 4 + 4 + 4 + piece_len));
        f[8] = kProtoVersion;
        put32(f + 9, req_id);
        put64(f + 13, chunk_id);
        put32(f + 21, static_cast<uint32_t>(pos));
        put32(f + 25, crcs[piece_idx++]);
        put32(f + 29, static_cast<uint32_t>(piece_len));
        std::memcpy(f + kPre, data + (pos - offset), piece_len);
        if (!send_all_poll(sock_fd, f, kPre + piece_len, deadline)) return -1;
        pos = piece_end;
    }
    uint8_t st[8 + 1 + 4 + 8 + 1];
    put32(st, kTypeReadStatus);
    put32(st + 4, 1 + 4 + 8 + 1);
    st[8] = kProtoVersion;
    put32(st + 9, req_id);
    put64(st + 13, chunk_id);
    st[21] = kStatusOk;
    return send_all_poll(sock_fd, st, sizeof(st), deadline) ? 0 : -1;
}

}  // extern "C"
