// Shared wire helpers for the native client/server sources.
//
// Frame = header(type:u32 BE, length:u32 BE) + version:u8 + body
// (lizardfs_tpu/proto/framing.py). Strings/bytes are u32-length-
// prefixed; lists are u32-count-prefixed (proto/codec.py).
// Trace propagation (runtime/tracing.py): data-plane REQUEST frames may
// carry a trailing u64 trace id after their fixed body — the reserved
// trailing region of the frame. Receivers that predate it ignore the
// extra bytes (body parsers bound-check ">= fixed size", not "=="); new
// receivers read it when the body is long enough. Trace id 0 = untraced.
// Session propagation (runtime/accounting.py): a second trailing u64 —
// the originating client session — follows the trace id under the same
// contract (per-session op accounting; it is positional, so a session
// only rides frames that also carry the trace slot). 0 = unattributed.
// The python codec mirrors both as SKEW_TOLERANT trailing fields.
// Trace DRAIN contract (serve_native.cpp TraceOp): finished ops flatten
// to u64 slots {kind, trace_id, chunk_id, bytes, t_start_us, t_end_us,
// disk_us, net_us, session_id, queue_us}. lz_serve_trace drains 8
// slots, lz_serve_trace2 adds session_id (9), lz_serve_trace3 adds
// queue_us (10) — the op's QoS pacing wait, folded into the "queue"
// attribution bucket. Additive only: python drains prefer the widest
// export present and fall back down the chain on a stale .so.
#pragma once

#include <cctype>
#include <cerrno>
#include <cstddef>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <ctime>
#include <netdb.h>
#include <vector>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <string>
#include <sys/socket.h>
#include <sys/un.h>
#include <unistd.h>

namespace lzwire {

constexpr uint8_t kProtoVersion = 1;

// CLOCK_REALTIME microseconds: span timestamps must merge across
// processes on the same host, so wall clock — not monotonic — by design
// (matches python's time.time() in runtime/tracing.py).
inline uint64_t now_us() {
    struct timespec ts;
    ::clock_gettime(CLOCK_REALTIME, &ts);
    return static_cast<uint64_t>(ts.tv_sec) * 1000000ull +
           static_cast<uint64_t>(ts.tv_nsec) / 1000ull;
}

inline void put16(uint8_t* p, uint16_t v) { p[0] = v >> 8; p[1] = v; }
inline void put32(uint8_t* p, uint32_t v) {
    p[0] = v >> 24; p[1] = v >> 16; p[2] = v >> 8; p[3] = v;
}
inline void put64(uint8_t* p, uint64_t v) {
    put32(p, static_cast<uint32_t>(v >> 32));
    put32(p + 4, static_cast<uint32_t>(v));
}
inline uint16_t get16(const uint8_t* p) {
    return static_cast<uint16_t>((p[0] << 8) | p[1]);
}
inline uint32_t get32(const uint8_t* p) {
    return (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
           (uint32_t(p[2]) << 8) | uint32_t(p[3]);
}
inline uint64_t get64(const uint8_t* p) {
    return (uint64_t(get32(p)) << 32) | get32(p + 4);
}

inline bool send_all(int fd, const uint8_t* buf, size_t len) {
    while (len) {
        ssize_t n = ::send(fd, buf, len, MSG_NOSIGNAL);
        if (n <= 0) {
            if (n < 0 && errno == EINTR) continue;
            return false;
        }
        buf += n;
        len -= static_cast<size_t>(n);
    }
    return true;
}

inline bool recv_all(int fd, uint8_t* buf, size_t len) {
    while (len) {
        ssize_t n = ::recv(fd, buf, len, 0);
        if (n <= 0) {
            if (n < 0 && errno == EINTR) continue;
            return false;
        }
        buf += n;
        len -= static_cast<size_t>(n);
    }
    return true;
}

inline int connect_tcp(const std::string& host, uint16_t port) {
    struct addrinfo hints {};
    hints.ai_family = AF_UNSPEC;
    hints.ai_socktype = SOCK_STREAM;
    char portstr[8];
    std::snprintf(portstr, sizeof(portstr), "%u", port);
    struct addrinfo* res = nullptr;
    if (::getaddrinfo(host.c_str(), portstr, &hints, &res) != 0) return -1;
    int fd = -1;
    for (struct addrinfo* ai = res; ai != nullptr; ai = ai->ai_next) {
        fd = ::socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
        if (fd < 0) continue;
        if (::connect(fd, ai->ai_addr, ai->ai_addrlen) == 0) break;
        ::close(fd);
        fd = -1;
    }
    ::freeaddrinfo(res);
    if (fd >= 0) {
        int one = 1;
        ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        int bufsz = 4 * 1024 * 1024;
        ::setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &bufsz, sizeof(bufsz));
        ::setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &bufsz, sizeof(bufsz));
    }
    return fd;
}

// --- same-host data-plane fast path (abstract unix sockets) ---------------
//
// Name contract: "lzfs-data-<advertised-host>-<port>", host checked
// against exactly {"127.0.0.1", "localhost"} — the ONE C copy of the
// contract; serve_native.cpp binds with uds_data_addr and relays via
// connect_data, and lizardfs_tpu/core/native_io.py mirrors it in
// Python (pinned by tests/test_fast_paths.py::test_uds_fast_path_
// engages and the FUSE read-pool tests). Master links must use
// connect_tcp — only the data plane binds a unix listener.

inline bool uds_disabled() {
    // Four-spelling parity with native_io.uds_disabled(): LZ_NO_UDS
    // set to 0/off/false/no means NOT disabled — the old presence
    // check treated "0" as set-and-therefore-kill, inverting the
    // documented contract (kill-switch lint class). Cached once: the
    // gate sits on every data dial.
    static const bool off = [] {
        const char* v = std::getenv("LZ_NO_UDS");
        if (v == nullptr) return false;
        char low[8] = {};
        for (size_t i = 0; i < sizeof(low) - 1 && v[i] != '\0'; ++i)
            low[i] = static_cast<char>(
                std::tolower(static_cast<unsigned char>(v[i])));
        return std::strcmp(low, "0") != 0 && std::strcmp(low, "off") != 0 &&
               std::strcmp(low, "false") != 0 && std::strcmp(low, "no") != 0;
    }();
    return off;
}

inline bool uds_host(const std::string& host) {
    return host == "127.0.0.1" || host == "localhost";
}

inline socklen_t uds_data_addr(const std::string& host, uint16_t port,
                               struct sockaddr_un* ua) {
    std::memset(ua, 0, sizeof(*ua));
    ua->sun_family = AF_UNIX;
    char name[96];
    int n = std::snprintf(name, sizeof(name), "lzfs-data-%s-%u",
                          host.c_str(), port);
    if (n <= 0 || n > 90) return 0;
    std::memcpy(ua->sun_path + 1, name, static_cast<size_t>(n));
    return static_cast<socklen_t>(
        offsetof(struct sockaddr_un, sun_path) + 1 + n);
}

// DATA-plane connect: same-host dials prefer the chunkserver's abstract
// unix listener (~2.5x less per-byte CPU than loopback TCP), falling
// back to TCP when absent, disabled, or owned by another uid (abstract
// names bypass filesystem permissions, so the peer is VERIFIED via
// SO_PEERCRED: only a server running as our own uid — or root — may
// serve us, anything else is a potential local impostor).
inline int connect_data(const std::string& host, uint16_t port) {
    if (uds_host(host) && !uds_disabled()) {
        int fd = ::socket(AF_UNIX, SOCK_STREAM, 0);
        if (fd >= 0) {
            struct sockaddr_un ua;
            socklen_t len = uds_data_addr(host, port, &ua);
            if (len > 0 &&
                ::connect(fd, reinterpret_cast<struct sockaddr*>(&ua),
                          len) == 0) {
                struct ucred uc {};
                socklen_t ul = sizeof(uc);
                if (::getsockopt(fd, SOL_SOCKET, SO_PEERCRED, &uc, &ul)
                        == 0 &&
                    (uc.uid == ::geteuid() || uc.uid == 0)) {
                    int bufsz = 4 * 1024 * 1024;
                    ::setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &bufsz,
                                 sizeof(bufsz));
                    ::setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &bufsz,
                                 sizeof(bufsz));
                    return fd;
                }
            }
            ::close(fd);
        }
    }
    return connect_tcp(host, port);
}

// Growable message builder for request bodies.
class Msg {
  public:
    explicit Msg(uint32_t type) : type_(type) {
        buf_.resize(9);
        buf_[8] = kProtoVersion;
    }
    Msg& u8(uint8_t v) { buf_.push_back(v); return *this; }
    Msg& u16(uint16_t v) {
        size_t n = buf_.size();
        buf_.resize(n + 2);
        put16(buf_.data() + n, v);
        return *this;
    }
    Msg& u32(uint32_t v) {
        size_t n = buf_.size();
        buf_.resize(n + 4);
        put32(buf_.data() + n, v);
        return *this;
    }
    Msg& u64(uint64_t v) {
        size_t n = buf_.size();
        buf_.resize(n + 8);
        put64(buf_.data() + n, v);
        return *this;
    }
    Msg& str(const std::string& s) {
        u32(static_cast<uint32_t>(s.size()));
        buf_.insert(buf_.end(), s.begin(), s.end());
        return *this;
    }
    Msg& u32list(const uint32_t* v, uint32_t n) {
        u32(n);
        for (uint32_t i = 0; i < n; ++i) u32(v[i]);
        return *this;
    }
    // The finished frame (header filled in), for a caller that sends it
    // itself.
    const std::vector<uint8_t>& frame() {
        put32(buf_.data(), type_);
        put32(buf_.data() + 4, static_cast<uint32_t>(buf_.size() - 8));
        return buf_;
    }
    bool send(int fd) {
        const std::vector<uint8_t>& f = frame();
        return send_all(fd, f.data(), f.size());
    }

  private:
    uint32_t type_;
    std::vector<uint8_t> buf_;
};

// Cursor over a received payload (starts after the version byte).
class Reader {
  public:
    Reader(const uint8_t* p, size_t n) : p_(p), n_(n) {}
    bool ok() const { return ok_; }
    uint8_t u8() { return ok_ && need(1) ? p_[pos_++] : 0; }
    uint16_t u16() {
        if (!need(2)) return 0;
        uint16_t v = get16(p_ + pos_);
        pos_ += 2;
        return v;
    }
    uint32_t u32() {
        if (!need(4)) return 0;
        uint32_t v = get32(p_ + pos_);
        pos_ += 4;
        return v;
    }
    uint64_t u64() {
        if (!need(8)) return 0;
        uint64_t v = get64(p_ + pos_);
        pos_ += 8;
        return v;
    }
    std::string str() {
        uint32_t n = u32();
        if (!need(n)) return "";
        std::string s(reinterpret_cast<const char*>(p_ + pos_), n);
        pos_ += n;
        return s;
    }

  private:
    bool need(size_t n) {
        if (pos_ + n > n_) {
            ok_ = false;
            return false;
        }
        return true;
    }
    const uint8_t* p_;
    size_t n_;
    size_t pos_ = 0;
    bool ok_ = true;
};

// Read one frame; payload (incl. version byte) lands in out. Returns
// the message type or 0 on socket error.
inline uint32_t recv_frame(int fd, std::vector<uint8_t>* out,
                           size_t max = 128u << 20) {
    uint8_t header[8];
    if (!recv_all(fd, header, 8)) return 0;
    uint32_t type = get32(header);
    uint32_t length = get32(header + 4);
    if (length < 1 || length > max) return 0;
    out->resize(length);
    if (!recv_all(fd, out->data(), length)) return 0;
    if ((*out)[0] != kProtoVersion) return 0;
    return type;
}

}  // namespace lzwire
