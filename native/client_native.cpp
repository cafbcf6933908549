// C client library implementation (see lizardfs_client.h).
//
// The analog of the reference's liblizardfs-client
// (src/mount/client/client.cc behind lizardfs_c_api.h): master control
// RPCs speak the cltoma/matocl protocol, file data rides the native
// bulk data plane (lz_read_part_bulk / lz_write_part* from
// io_native.cpp, against the C++ chunkserver data-plane listener) — an
// external consumer links this and never touches Python.
//
// Threading: one mutex per handle; operations serialize. Data-plane
// sockets are pooled per address inside the handle.
//
// Master-RPC wire layouts (keep in sync with proto/messages.py — the
// `lizardfs-lint` native-wire checker cross-checks every declaration
// against the catalog; str/list fields are u32-length/count-prefixed,
// trailing skew-tolerant fields — replica_ok, meta_version, trace_id —
// may be elided on the wire and are default-filled by the receiver):
//   CltomaRegister(1000): req_id:u32 session_id:u64 info:str password:str
//                         replica_ok:u8
//   MatoclRegister(1001): req_id:u32 status:u8 session_id:u64
//                         meta_version:u64
//   CltomaLookup(1002): req_id:u32 parent:u32 name:str uid:u32 gids:list:u32
//   MatoclAttrReply(1003): req_id:u32 status:u8 attr:msg:Attr
//   CltomaGetattr(1004): req_id:u32 inode:u32
//   CltomaMkdir(1006): req_id:u32 parent:u32 name:str mode:u16 uid:u32
//                      gid:u32
//   CltomaCreate(1008): req_id:u32 parent:u32 name:str mode:u16 uid:u32
//                       gid:u32
//   CltomaReaddir(1010): req_id:u32 inode:u32 uid:u32 gids:list:u32
//   MatoclReaddir(1011): req_id:u32 status:u8 entries:list:msg:DirEntry
//                        meta_version:u64
//   CltomaUnlink(1012): req_id:u32 parent:u32 name:str uid:u32 gids:list:u32
//   MatoclStatusReply(1013): req_id:u32 status:u8 meta_version:u64
//   CltomaRmdir(1014): req_id:u32 parent:u32 name:str uid:u32 gids:list:u32
//   CltomaRename(1016): req_id:u32 parent_src:u32 name_src:str
//                       parent_dst:u32 name_dst:str uid:u32 gids:list:u32
//   CltomaReadChunk(1020): req_id:u32 inode:u32 chunk_index:u32 uid:u32
//                          gids:list:u32 trace_id:u64
//   MatoclReadChunk(1021): req_id:u32 status:u8 chunk_id:u64 version:u32
//                          file_length:u64 locations:list:msg:PartLocation
//                          meta_version:u64 srv_us:u32 content_gen:u64
//   CltomaWriteChunk(1022): req_id:u32 inode:u32 chunk_index:u32 uid:u32
//                           gids:list:u32 trace_id:u64
//   MatoclWriteChunk(1023): req_id:u32 status:u8 chunk_id:u64 version:u32
//                           file_length:u64 locations:list:msg:PartLocation
//                           srv_us:u32
//   CltomaWriteChunkEnd(1024): req_id:u32 chunk_id:u64 inode:u32
//                              chunk_index:u32 file_length:u64 status:u8
//                              trace_id:u64
//   CltomaTruncate(1026): req_id:u32 inode:u32 length:u64 uid:u32
//                         gids:list:u32
//   CltomaSetattr(1028): req_id:u32 inode:u32 set_mask:u8 mode:u16 uid:u32
//                        gid:u32 atime:u32 mtime:u32 trash_time:u32
//                        caller_uid:u32 caller_gids:list:u32
//   CltomaSymlink(1030): req_id:u32 parent:u32 name:str target:str uid:u32
//                        gid:u32
//   CltomaReadlink(1032): req_id:u32 inode:u32
//   MatoclReadlink(1033): req_id:u32 status:u8 target:str meta_version:u64
//   CltomaLink(1034): req_id:u32 inode:u32 parent:u32 name:str uid:u32
//                     gids:list:u32
//   CltomaAccess(1060): req_id:u32 inode:u32 uid:u32 gids:list:u32 mask:u8
//   CltomaGoodbye(1066): req_id:u32

#include "lizardfs_client.h"

#include <algorithm>
#include <atomic>
#include <map>
#include <mutex>
#include <string>
#include <vector>

#include "wire.h"

extern "C" {
int lz_read_part(int fd, uint64_t chunk_id, uint32_t version,
                 uint32_t part_id, uint32_t offset, uint32_t size,
                 uint8_t* out);
int lz_read_part_bulk(int fd, uint64_t chunk_id, uint32_t version,
                      uint32_t part_id, uint32_t offset, uint32_t size,
                      uint8_t* out);
int lz_write_part(int fd, uint64_t chunk_id, const uint8_t* payload,
                  uint64_t len, uint64_t part_offset, uint32_t first_write_id);
int lz_write_part_bulk(int fd, uint64_t chunk_id, const uint8_t* payload,
                       uint64_t len, uint64_t part_offset, uint32_t write_id);
}

namespace {

using namespace lzwire;

constexpr uint32_t kBlockSize = 64 * 1024;
constexpr uint64_t kChunkSize = 64ull * 1024 * 1024;

// message types (lizardfs_tpu/proto/messages.py)
enum : uint32_t {
    kCltomaRegister = 1000,
    kMatoclRegister = 1001,
    kCltomaLookup = 1002,
    kMatoclAttrReply = 1003,
    kCltomaGetattr = 1004,
    kCltomaMkdir = 1006,
    kCltomaCreate = 1008,
    kCltomaReaddir = 1010,
    kMatoclReaddir = 1011,
    kCltomaUnlink = 1012,
    kMatoclStatusReply = 1013,
    kCltomaRmdir = 1014,
    kCltomaRename = 1016,
    kCltomaReadChunk = 1020,
    kMatoclReadChunk = 1021,
    kCltomaWriteChunk = 1022,
    kMatoclWriteChunk = 1023,
    kCltomaWriteChunkEnd = 1024,
    kCltomaTruncate = 1026,
    kCltomaSetattr = 1028,
    kCltomaSymlink = 1030,
    kCltomaReadlink = 1032,
    kMatoclReadlink = 1033,
    kCltomaLink = 1034,
    kCltomaAccess = 1060,
    kCltomaGoodbye = 1066,
    kCltocsWriteInit = 1210,
    kCstoclWriteStatus = 1212,
    kCltocsWriteEnd = 1213,
};

constexpr int kErrConn = -1;
constexpr int stOK = 0;
constexpr int stEINVAL = 5;
constexpr int stEIO = 9;
constexpr int stNOT_POSSIBLE = 29;

struct Location {
    std::string host;
    uint16_t port;
    uint32_t part_id;
};

struct ChunkGrant {
    int status = stEIO;
    uint64_t chunk_id = 0;
    uint32_t version = 0;
    uint64_t file_length = 0;
    std::vector<Location> locations;
};

// Bound every receive so a hung/partitioned master degrades into an
// error instead of blocking the embedding application forever.
static void set_recv_timeout(int fd, int seconds) {
    struct timeval tv {};
    tv.tv_sec = seconds;
    ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
}

}  // namespace

struct liz {
    std::mutex mu;
    int master_fd = -1;
    std::string host;
    int port = 0;
    std::string password;
    uint64_t session_id = 0;
    std::atomic<uint32_t> req_id{1};
    uint32_t uid = 0, gid = 0;
    std::map<std::pair<std::string, uint16_t>, int> data_fds;
    std::vector<uint8_t> payload;  // reusable reply buffer

    ~liz() {
        if (master_fd >= 0) ::close(master_fd);
        for (auto& kv : data_fds) ::close(kv.second);
    }

    int data_fd(const std::string& h, uint16_t p) {
        auto key = std::make_pair(h, p);
        auto it = data_fds.find(key);
        if (it != data_fds.end()) return it->second;
        int fd = connect_data(h, p);  // same-host unix fast path
        if (fd >= 0) {
            set_recv_timeout(fd, 30);
            data_fds[key] = fd;
        }
        return fd;
    }

    void drop_data_fd(const std::string& h, uint16_t p) {
        auto key = std::make_pair(h, p);
        auto it = data_fds.find(key);
        if (it != data_fds.end()) {
            ::close(it->second);
            data_fds.erase(it);
        }
    }

    // send a request and wait for its reply: the expected type, or the
    // generic MatoclStatusReply the master uses for error fallbacks.
    // Returns the type received (0 = connection failure). Pushed
    // messages (lock grants) are skipped.
    uint32_t call(Msg& msg, uint32_t expect_type) {
        if (master_fd < 0 && !reconnect()) return 0;
        if (!msg.send(master_fd)) {
            if (!reconnect() || !msg.send(master_fd)) return 0;
        }
        for (int i = 0; i < 64; ++i) {
            uint32_t type = recv_frame(master_fd, &payload);
            if (type == 0) return 0;
            if (type == expect_type || type == kMatoclStatusReply)
                return type;
        }
        return 0;
    }

    bool reconnect() {
        if (master_fd >= 0) ::close(master_fd);
        master_fd = connect_tcp(host, static_cast<uint16_t>(port));
        if (master_fd < 0) return false;
        set_recv_timeout(master_fd, 30);
        Msg reg(kCltomaRegister);
        reg.u32(req_id++).u64(session_id).str("libclient").str(password);
        if (!reg.send(master_fd)) return false;
        uint32_t type = recv_frame(master_fd, &payload);
        if (type != kMatoclRegister) return false;
        Reader r(payload.data() + 1, payload.size() - 1);
        r.u32();  // req_id
        if (r.u8() != stOK) return false;
        session_id = r.u64();
        return true;
    }
};

namespace {

int parse_attr(Reader* r, liz_attr_t* out) {
    // MatoclAttrReply: req_id status attr{inode ftype mode uid gid
    // atime mtime ctime nlink length goal trash_time}
    r->u32();
    int status = r->u8();
    liz_attr_t a{};
    a.inode = r->u32();
    a.ftype = r->u8();
    a.mode = r->u16();
    a.uid = r->u32();
    a.gid = r->u32();
    a.atime = r->u32();
    a.mtime = r->u32();
    a.ctime = r->u32();
    a.nlink = r->u32();
    a.length = r->u64();
    a.goal = r->u8();
    a.trash_time = r->u32();
    if (!r->ok()) return kErrConn;
    if (status == stOK && out != nullptr) *out = a;
    return status;
}

int attr_call(liz_t* fs, Msg& msg, liz_attr_t* out) {
    std::lock_guard<std::mutex> g(fs->mu);
    uint32_t type = fs->call(msg, kMatoclAttrReply);
    if (type == 0) return kErrConn;
    Reader r(fs->payload.data() + 1, fs->payload.size() - 1);
    if (type == kMatoclStatusReply) {  // error fallback reply
        r.u32();
        int status = r.u8();
        return r.ok() && status != stOK ? status : kErrConn;
    }
    return parse_attr(&r, out);
}

int status_call(liz_t* fs, Msg& msg) {
    std::lock_guard<std::mutex> g(fs->mu);
    if (fs->call(msg, kMatoclStatusReply) == 0) return kErrConn;
    Reader r(fs->payload.data() + 1, fs->payload.size() - 1);
    r.u32();
    int status = r.u8();
    return r.ok() ? status : kErrConn;
}

ChunkGrant chunk_call(liz_t* fs, uint32_t type, uint32_t reply_type,
                      uint32_t inode, uint32_t chunk_index) {
    ChunkGrant out;
    Msg msg(type);
    msg.u32(fs->req_id++).u32(inode).u32(chunk_index).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    uint32_t got = fs->call(msg, reply_type);
    if (got == 0) {
        out.status = kErrConn;
        return out;
    }
    Reader r(fs->payload.data() + 1, fs->payload.size() - 1);
    if (got == kMatoclStatusReply) {
        r.u32();
        int status = r.u8();
        out.status = r.ok() && status != stOK ? status : kErrConn;
        return out;
    }
    r.u32();
    out.status = r.u8();
    out.chunk_id = r.u64();
    out.version = r.u32();
    out.file_length = r.u64();
    uint32_t n = r.u32();
    for (uint32_t i = 0; i < n && r.ok() && i < 256; ++i) {
        Location loc;
        loc.host = r.str();
        loc.port = r.u16();
        loc.part_id = r.u32();
        out.locations.push_back(std::move(loc));
    }
    if (!r.ok()) out.status = kErrConn;
    return out;
}

// slice geometry (core/geometry.py)
inline int slice_type_of(uint32_t part_id) { return part_id / 64; }
inline int part_index_of(uint32_t part_id) { return part_id % 64; }
inline bool type_is_xor(int t) { return t >= 2 && t <= 9; }
inline bool type_is_ec(int t) { return t >= 10 && t < 10 + 31 * 32; }
inline int data_parts_of(int t) {
    if (type_is_xor(t)) return t;
    if (type_is_ec(t)) return 2 + (t - 10) / 32;
    return 1;
}

// read [off, off+size) of one chunk into buf; range is caller-clipped
int read_chunk_range(liz_t* fs, const ChunkGrant& g, uint64_t off,
                     uint64_t size, uint8_t* buf) {
    if (g.chunk_id == 0) {  // hole
        std::memset(buf, 0, size);
        return stOK;
    }
    int slice = g.locations.empty() ? 0 : slice_type_of(g.locations[0].part_id);
    if (slice == 0) {
        // standard: any copy serves the byte range directly
        int last = stEIO;
        for (const auto& loc : g.locations) {
            int fd = fs->data_fd(loc.host, loc.port);
            if (fd < 0) {
                last = kErrConn;
                continue;
            }
            int rc = (off % kBlockSize == 0 ? lz_read_part_bulk : lz_read_part)(
                fd, g.chunk_id, g.version, loc.part_id,
                static_cast<uint32_t>(off), static_cast<uint32_t>(size), buf);
            if (rc == 0) return stOK;
            fs->drop_data_fd(loc.host, loc.port);
            last = rc < 0 ? kErrConn : rc;
        }
        return last;
    }
    // striped: interleave blocks from the data parts (all must be
    // live; degraded reads need the recovery planner — FUSE path)
    int d = data_parts_of(slice);
    int first_data = type_is_xor(slice) ? 1 : 0;
    std::map<int, const Location*> by_index;
    for (const auto& loc : g.locations) {
        int idx = part_index_of(loc.part_id);
        if (idx >= first_data && idx < first_data + d)
            by_index.emplace(idx - first_data, &loc);
    }
    if (static_cast<int>(by_index.size()) < d) return stNOT_POSSIBLE;
    uint64_t lo_block = off / kBlockSize;
    uint64_t hi_block = (off + size - 1) / kBlockSize;
    uint64_t lo_slot = lo_block / d, hi_slot = hi_block / d;
    uint32_t nslots = static_cast<uint32_t>(hi_slot - lo_slot + 1);
    std::vector<std::vector<uint8_t>> parts(d);
    for (int i = 0; i < d; ++i) {
        const Location* loc = by_index[i];
        int fd = fs->data_fd(loc->host, loc->port);
        if (fd < 0) return kErrConn;
        parts[i].resize(static_cast<size_t>(nslots) * kBlockSize);
        int rc = lz_read_part_bulk(
            fd, g.chunk_id, g.version, loc->part_id,
            static_cast<uint32_t>(lo_slot * kBlockSize),
            nslots * kBlockSize, parts[i].data());
        if (rc != 0) {
            fs->drop_data_fd(loc->host, loc->port);
            return rc < 0 ? kErrConn : rc;
        }
    }
    for (uint64_t b = lo_block; b <= hi_block; ++b) {
        int part = static_cast<int>(b % d);
        uint64_t slot = b / d - lo_slot;
        uint64_t block_start = b * kBlockSize;
        uint64_t s = std::max(off, block_start);
        uint64_t e = std::min(off + size, block_start + kBlockSize);
        std::memcpy(buf + (s - off),
                    parts[part].data() + slot * kBlockSize +
                        (s - block_start),
                    e - s);
    }
    return stOK;
}

// write [off, off+size) of one chunk (standard goals only)
int write_chunk_range(liz_t* fs, const ChunkGrant& g, uint32_t inode,
                      uint32_t chunk_index, uint64_t off, uint64_t size,
                      const uint8_t* buf, uint64_t new_file_length) {
    int slice = g.locations.empty() ? -1 : slice_type_of(g.locations[0].part_id);
    if (slice != 0) {
        // striped writes need the parity planner (FUSE path) — but the
        // grant already LOCKED the chunk; an error
        // WriteChunkEnd releases the lock instead of leaking it 30 s
        Msg endm(kCltomaWriteChunkEnd);
        endm.u32(fs->req_id++).u64(g.chunk_id).u32(inode).u32(chunk_index);
        endm.u64(g.file_length).u8(stEIO);
        fs->call(endm, kMatoclStatusReply);
        return stNOT_POSSIBLE;
    }
    // one chain through all copies (WriteExecutor analog)
    const Location& head = g.locations[0];
    int fd = connect_data(head.host, head.port);  // exclusive for the chain
    if (fd < 0) return kErrConn;
    int code = stEIO;
    do {
        Msg init(kCltocsWriteInit);
        init.u32(1).u64(g.chunk_id).u32(g.version).u32(head.part_id);
        init.u32(static_cast<uint32_t>(g.locations.size() - 1));
        for (size_t i = 1; i < g.locations.size(); ++i) {
            init.str(g.locations[i].host);
            init.u16(g.locations[i].port);
            init.u32(g.locations[i].part_id);
        }
        init.u8(0);  // create=False: the master created the parts
        if (!init.send(fd)) {
            code = kErrConn;
            break;
        }
        std::vector<uint8_t> reply;
        if (recv_frame(fd, &reply) != kCstoclWriteStatus) {
            code = kErrConn;
            break;
        }
        Reader r(reply.data() + 1, reply.size() - 1);
        r.u32();
        r.u64();
        r.u32();
        int st0 = r.u8();
        if (st0 != stOK) {
            code = st0;
            break;
        }
        int rc = (off % kBlockSize == 0 ? lz_write_part_bulk : lz_write_part)(
            fd, g.chunk_id, buf, size, off, 1);
        if (rc != 0) {
            code = rc < 0 ? kErrConn : rc;
            break;
        }
        Msg end(kCltocsWriteEnd);
        end.u32(0).u64(g.chunk_id);
        if (!end.send(fd) || recv_frame(fd, &reply) != kCstoclWriteStatus) {
            code = kErrConn;
            break;
        }
        Reader re(reply.data() + 1, reply.size() - 1);
        re.u32();
        re.u64();
        re.u32();
        code = re.u8();
    } while (false);
    ::close(fd);

    // WriteChunkEnd commits the new length and unlocks the chunk
    Msg endm(kCltomaWriteChunkEnd);
    endm.u32(fs->req_id++).u64(g.chunk_id).u32(inode).u32(chunk_index);
    endm.u64(new_file_length).u8(static_cast<uint8_t>(code == stOK ? 0 : 9));
    if (fs->call(endm, kMatoclStatusReply) == 0) return kErrConn;
    return code;
}

}  // namespace

extern "C" {

liz_t* liz_init(const char* host, int port, const char* password) {
    liz_t* fs = new liz_t();
    fs->host = host;
    fs->port = port;
    fs->password = password != nullptr ? password : "";
    if (!fs->reconnect()) {
        delete fs;
        return nullptr;
    }
    return fs;
}

void liz_destroy(liz_t* fs) {
    if (fs == nullptr) return;
    {
        std::lock_guard<std::mutex> g(fs->mu);
        if (fs->master_fd >= 0) {
            // clean goodbye (releases our locks server-side), best
            // effort with a short bound so destroy can never hang:
            // one send + one recv on the EXISTING fd — never call()
            // (it would reconnect, blocking in connect with no bound)
            set_recv_timeout(fs->master_fd, 2);
            Msg bye(kCltomaGoodbye);
            bye.u32(fs->req_id++);
            if (bye.send(fs->master_fd)) {
                recv_frame(fs->master_fd, &fs->payload);
            }
        }
    }
    delete fs;
}

void liz_set_identity(liz_t* fs, uint32_t uid, uint32_t gid) {
    std::lock_guard<std::mutex> g(fs->mu);
    fs->uid = uid;
    fs->gid = gid;
}

int liz_lookup(liz_t* fs, uint32_t parent, const char* name, liz_attr_t* out) {
    Msg msg(kCltomaLookup);
    msg.u32(fs->req_id++).u32(parent).str(name).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    return attr_call(fs, msg, out);
}

int liz_getattr(liz_t* fs, uint32_t inode, liz_attr_t* out) {
    Msg msg(kCltomaGetattr);
    msg.u32(fs->req_id++).u32(inode);
    return attr_call(fs, msg, out);
}

int liz_mkdir(liz_t* fs, uint32_t parent, const char* name, uint16_t mode,
              liz_attr_t* out) {
    Msg msg(kCltomaMkdir);
    msg.u32(fs->req_id++).u32(parent).str(name).u16(mode).u32(fs->uid)
        .u32(fs->gid);
    return attr_call(fs, msg, out);
}

int liz_create(liz_t* fs, uint32_t parent, const char* name, uint16_t mode,
               liz_attr_t* out) {
    Msg msg(kCltomaCreate);
    msg.u32(fs->req_id++).u32(parent).str(name).u16(mode).u32(fs->uid)
        .u32(fs->gid);
    return attr_call(fs, msg, out);
}

int liz_unlink(liz_t* fs, uint32_t parent, const char* name) {
    Msg msg(kCltomaUnlink);
    msg.u32(fs->req_id++).u32(parent).str(name).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    return status_call(fs, msg);
}

int liz_rmdir(liz_t* fs, uint32_t parent, const char* name) {
    Msg msg(kCltomaRmdir);
    msg.u32(fs->req_id++).u32(parent).str(name).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    return status_call(fs, msg);
}

int liz_rename(liz_t* fs, uint32_t parent_src, const char* name_src,
               uint32_t parent_dst, const char* name_dst) {
    Msg msg(kCltomaRename);
    msg.u32(fs->req_id++).u32(parent_src).str(name_src).u32(parent_dst)
        .str(name_dst).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    return status_call(fs, msg);
}

int liz_symlink(liz_t* fs, uint32_t parent, const char* name,
                const char* target, liz_attr_t* out) {
    Msg msg(kCltomaSymlink);
    msg.u32(fs->req_id++).u32(parent).str(name).str(target).u32(fs->uid)
        .u32(fs->gid);
    return attr_call(fs, msg, out);
}

int liz_readlink(liz_t* fs, uint32_t inode, char* buf, uint32_t bufsize) {
    Msg msg(kCltomaReadlink);
    msg.u32(fs->req_id++).u32(inode);
    std::lock_guard<std::mutex> g(fs->mu);
    uint32_t got = fs->call(msg, kMatoclReadlink);
    if (got == 0) return kErrConn;
    Reader r(fs->payload.data() + 1, fs->payload.size() - 1);
    if (got == kMatoclStatusReply) {
        r.u32();
        int status = r.u8();
        return r.ok() && status != stOK ? status : kErrConn;
    }
    r.u32();
    int status = r.u8();
    std::string target = r.str();
    if (!r.ok()) return kErrConn;
    if (status != stOK) return status;
    if (target.size() + 1 > bufsize) return stEINVAL;
    std::memcpy(buf, target.c_str(), target.size() + 1);
    return stOK;
}

int liz_link(liz_t* fs, uint32_t inode, uint32_t parent, const char* name,
             liz_attr_t* out) {
    Msg msg(kCltomaLink);
    msg.u32(fs->req_id++).u32(inode).u32(parent).str(name).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    return attr_call(fs, msg, out);
}

int liz_readdir(liz_t* fs, uint32_t inode, uint32_t offset,
                liz_direntry_t* entries, uint32_t max, uint32_t* n) {
    Msg msg(kCltomaReaddir);
    msg.u32(fs->req_id++).u32(inode).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    std::lock_guard<std::mutex> g(fs->mu);
    uint32_t got = fs->call(msg, kMatoclReaddir);
    if (got == 0) return kErrConn;
    Reader r(fs->payload.data() + 1, fs->payload.size() - 1);
    if (got == kMatoclStatusReply) {
        r.u32();
        int status = r.u8();
        return r.ok() && status != stOK ? status : kErrConn;
    }
    r.u32();
    int status = r.u8();
    uint32_t count = r.u32();
    if (status != stOK) return status;
    uint32_t out_n = 0;
    for (uint32_t i = 0; i < count && r.ok(); ++i) {
        std::string name = r.str();
        uint32_t child = r.u32();
        uint8_t ftype = r.u8();
        if (i < offset || out_n >= max) continue;
        liz_direntry_t* e = &entries[out_n++];
        std::snprintf(e->name, sizeof(e->name), "%s", name.c_str());
        e->inode = child;
        e->ftype = ftype;
    }
    if (!r.ok()) return kErrConn;
    *n = out_n;
    return stOK;
}

int liz_setattr(liz_t* fs, uint32_t inode, uint8_t set_mask, uint16_t mode,
                uint32_t uid, uint32_t gid, uint32_t atime, uint32_t mtime,
                liz_attr_t* out) {
    Msg msg(kCltomaSetattr);
    msg.u32(fs->req_id++).u32(inode).u8(set_mask).u16(mode).u32(uid).u32(gid)
        .u32(atime).u32(mtime).u32(0 /* trash_time */).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    return attr_call(fs, msg, out);
}

int liz_truncate(liz_t* fs, uint32_t inode, uint64_t length) {
    Msg msg(kCltomaTruncate);
    msg.u32(fs->req_id++).u32(inode).u64(length).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    return attr_call(fs, msg, nullptr);
}

int liz_access(liz_t* fs, uint32_t inode, uint8_t mask) {
    Msg msg(kCltomaAccess);
    msg.u32(fs->req_id++).u32(inode).u32(fs->uid);
    uint32_t gids[1] = {fs->gid};
    msg.u32list(gids, 1);
    msg.u8(mask);
    return status_call(fs, msg);
}

int64_t liz_read(liz_t* fs, uint32_t inode, uint64_t offset, uint64_t size,
                 uint8_t* buf) {
    std::lock_guard<std::mutex> g(fs->mu);
    uint64_t done = 0;
    while (done < size) {
        uint64_t pos = offset + done;
        uint32_t ci = static_cast<uint32_t>(pos / kChunkSize);
        ChunkGrant grant =
            chunk_call(fs, kCltomaReadChunk, kMatoclReadChunk, inode, ci);
        if (grant.status != stOK)
            return done ? static_cast<int64_t>(done)
                        : (grant.status < 0 ? kErrConn : -grant.status);
        if (pos >= grant.file_length) break;  // EOF
        uint64_t coff = pos % kChunkSize;
        uint64_t chunk_len =
            std::min<uint64_t>(grant.file_length - ci * kChunkSize, kChunkSize);
        uint64_t take =
            std::min({size - done, kChunkSize - coff, chunk_len - coff});
        int rc = read_chunk_range(fs, grant, coff, take, buf + done);
        if (rc != stOK)
            return done ? static_cast<int64_t>(done)
                        : (rc < 0 ? kErrConn : -rc);
        done += take;
    }
    return static_cast<int64_t>(done);
}

int64_t liz_write(liz_t* fs, uint32_t inode, uint64_t offset, uint64_t size,
                  const uint8_t* buf) {
    std::lock_guard<std::mutex> g(fs->mu);
    uint64_t done = 0;
    while (done < size) {
        uint64_t pos = offset + done;
        uint32_t ci = static_cast<uint32_t>(pos / kChunkSize);
        uint64_t coff = pos % kChunkSize;
        uint64_t take = std::min(size - done, kChunkSize - coff);
        ChunkGrant grant =
            chunk_call(fs, kCltomaWriteChunk, kMatoclWriteChunk, inode, ci);
        if (grant.status != stOK)
            return done ? static_cast<int64_t>(done)
                        : (grant.status < 0 ? kErrConn : -grant.status);
        uint64_t new_len = std::max(grant.file_length, pos + take);
        int rc = write_chunk_range(fs, grant, inode, ci, coff, take,
                                   buf + done, new_len);
        if (rc != stOK)
            return done ? static_cast<int64_t>(done)
                        : (rc < 0 ? kErrConn : -rc);
        done += take;
    }
    return static_cast<int64_t>(done);
}

const char* liz_strerror(int code) {
    switch (code < 0 ? -code : code) {
        case 0: return "OK";
        case 1: return "EPERM";
        case 2: return "ENOENT";
        case 3: return "EACCES";
        case 4: return "EEXIST";
        case 5: return "EINVAL";
        case 6: return "ENOTDIR";
        case 7: return "EISDIR";
        case 8: return "ENOSPC";
        case 9: return "EIO";
        case 10: return "ENOTEMPTY";
        case 16: return "NO_CHUNK";
        case 19: return "WRONG_VERSION";
        case 20: return "CRC_ERROR";
        case 24: return "QUOTA_EXCEEDED";
        case 26: return "EROFS";
        case 29: return "NOT_POSSIBLE (striped data path: use FUSE)";
        default: return "lizardfs error";
    }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Minimal NFSv3 wire client (RFC 1813 over ONC-RPC/RFC 5531, TCP record
// marking, AUTH_SYS) — the non-Python measuring client for the NFS
// gateway. Scope: MNT + LOOKUP + CREATE + READ + WRITE + COMMIT, enough
// to drive dd-style throughput against the gateway without Python
// anywhere on the client side (comparing it with the asyncio client
// separates server cost from measuring-client cost).
// ---------------------------------------------------------------------------

namespace {

class XdrW {
  public:
    XdrW& u32(uint32_t v) {
        buf_.push_back(static_cast<uint8_t>(v >> 24));
        buf_.push_back(static_cast<uint8_t>(v >> 16));
        buf_.push_back(static_cast<uint8_t>(v >> 8));
        buf_.push_back(static_cast<uint8_t>(v));
        return *this;
    }
    XdrW& u64(uint64_t v) {
        u32(static_cast<uint32_t>(v >> 32));
        return u32(static_cast<uint32_t>(v));
    }
    XdrW& opaque(const uint8_t* p, uint32_t n) {
        u32(n);
        buf_.insert(buf_.end(), p, p + n);
        while (buf_.size() % 4) buf_.push_back(0);
        return *this;
    }
    XdrW& str(const char* s) {
        return opaque(reinterpret_cast<const uint8_t*>(s),
                      static_cast<uint32_t>(strlen(s)));
    }
    const std::vector<uint8_t>& bytes() const { return buf_; }

  private:
    std::vector<uint8_t> buf_;
};

class XdrR {
  public:
    XdrR(const uint8_t* p, size_t n) : p_(p), n_(n) {}
    bool ok() const { return ok_; }
    uint32_t u32() {
        if (pos_ + 4 > n_) { ok_ = false; return 0; }
        uint32_t v = (uint32_t(p_[pos_]) << 24) |
                     (uint32_t(p_[pos_ + 1]) << 16) |
                     (uint32_t(p_[pos_ + 2]) << 8) | p_[pos_ + 3];
        pos_ += 4;
        return v;
    }
    uint64_t u64() {
        uint64_t hi = u32();
        return (hi << 32) | u32();
    }
    void skip(size_t n) {
        n = (n + 3) & ~size_t(3);
        if (pos_ + n > n_) { ok_ = false; return; }
        pos_ += n;
    }
    // var-length opaque into out (bounded by cap); returns length
    uint32_t opaque(uint8_t* out, uint32_t cap) {
        uint32_t len = u32();
        if (!ok_ || pos_ + ((len + 3) & ~3u) > n_ || len > cap) {
            ok_ = false;
            return 0;
        }
        memcpy(out, p_ + pos_, len);
        pos_ += (len + 3) & ~3u;
        return len;
    }
    void skip_post_op_attr() {
        if (u32()) skip(84);  // fattr3 is 84 fixed bytes
    }
    void skip_wcc_data() {
        if (u32()) skip(24);  // pre_op wcc_attr
        skip_post_op_attr();
    }

  private:
    const uint8_t* p_;
    size_t n_;
    size_t pos_ = 0;
    bool ok_ = true;
};

enum : uint32_t {
    kProgNfs = 100003,
    kProgMount = 100005,
    kNfsLookup = 3,
    kNfsRead = 6,
    kNfsWrite = 7,
    kNfsCreate = 8,
    kNfsCommit = 21,
    kMntMnt = 1,
};

}  // namespace

struct liz_nfs {
    int fd = -1;
    uint32_t xid = 1;
    uint32_t uid = 0, gid = 0;
    std::vector<uint8_t> reply;
    std::mutex mu;

    ~liz_nfs() {
        if (fd >= 0) ::close(fd);
    }

    // one RPC round trip; returns the XDR results region (after the
    // rpc reply header) in `reply` via XdrR, or nullptr on failure
    bool call(uint32_t prog, uint32_t vers, uint32_t proc,
              const std::vector<uint8_t>& args) {
        XdrW hdr;
        uint32_t this_xid = xid++;
        hdr.u32(this_xid).u32(0).u32(2).u32(prog).u32(vers).u32(proc);
        // AUTH_SYS credential: stamp, machine, uid, gid, gids<1>
        XdrW cred;
        cred.u32(0).str("cclient").u32(uid).u32(gid).u32(1).u32(gid);
        hdr.u32(1).opaque(cred.bytes().data(),
                          static_cast<uint32_t>(cred.bytes().size()));
        hdr.u32(0).u32(0);  // verf AUTH_NONE
        std::vector<uint8_t> rec;
        uint32_t total =
            static_cast<uint32_t>(hdr.bytes().size() + args.size());
        rec.reserve(4 + total);
        uint32_t mark = 0x80000000u | total;  // single last fragment
        rec.push_back(static_cast<uint8_t>(mark >> 24));
        rec.push_back(static_cast<uint8_t>(mark >> 16));
        rec.push_back(static_cast<uint8_t>(mark >> 8));
        rec.push_back(static_cast<uint8_t>(mark));
        rec.insert(rec.end(), hdr.bytes().begin(), hdr.bytes().end());
        rec.insert(rec.end(), args.begin(), args.end());
        if (!send_all(fd, rec.data(), rec.size())) return false;
        // reassemble the reply record (fragments until the last bit)
        reply.clear();
        for (;;) {
            uint8_t mh[4];
            if (!recv_all(fd, mh, 4)) return false;
            uint32_t m = (uint32_t(mh[0]) << 24) | (uint32_t(mh[1]) << 16) |
                         (uint32_t(mh[2]) << 8) | mh[3];
            uint32_t len = m & 0x7fffffffu;
            size_t base = reply.size();
            reply.resize(base + len);
            if (len && !recv_all(fd, reply.data() + base, len)) return false;
            if (m & 0x80000000u) break;
        }
        // rpc reply header: xid, REPLY(1), MSG_ACCEPTED(0),
        // verf(flavor+opaque), SUCCESS(0)
        XdrR r(reply.data(), reply.size());
        if (r.u32() != this_xid || r.u32() != 1 || r.u32() != 0)
            return false;
        r.u32();
        uint32_t vlen = r.u32();
        r.skip(vlen);
        if (r.u32() != 0 || !r.ok()) return false;
        // record where the XDR results start (behind xid + REPLY +
        // accepted + verf(flavor + padded opaque) + accept_stat) so
        // result parsers never re-derive the header layout
        results_off = 5 * 4 + ((vlen + 3) & ~3u) + 4;
        return true;
    }

    size_t results_off = 0;  // set by call(): start of the results region
};

extern "C" {

liz_nfs_t* liz_nfs_connect(const char* host, int port, uint32_t uid,
                           uint32_t gid) {
    auto* h = new liz_nfs();
    h->fd = connect_tcp(host, static_cast<uint16_t>(port));
    if (h->fd < 0) {
        delete h;
        return nullptr;
    }
    set_recv_timeout(h->fd, 30);
    h->uid = uid;
    h->gid = gid;
    return h;
}

void liz_nfs_close(liz_nfs_t* h) { delete h; }

int liz_nfs_mount(liz_nfs_t* h, const char* path, uint8_t* fh_out,
                  uint32_t* fh_len) {
    std::lock_guard<std::mutex> g(h->mu);
    XdrW args;
    args.str(path);
    if (!h->call(kProgMount, 3, kMntMnt, args.bytes())) return -1;
    size_t off = h->results_off;
    XdrR r(h->reply.data() + off, h->reply.size() - off);
    uint32_t status = r.u32();
    if (status != 0) return static_cast<int>(status);
    *fh_len = r.opaque(fh_out, 64);
    return r.ok() ? 0 : -1;
}

static int nfs_fh_result(liz_nfs_t* h, uint8_t* fh_out, uint32_t* fh_len,
                         bool post_op_fh) {
    size_t off = h->results_off;
    XdrR r(h->reply.data() + off, h->reply.size() - off);
    uint32_t status = r.u32();
    if (status != 0) return static_cast<int>(status);
    if (post_op_fh && r.u32() == 0) return -1;  // handle must follow
    *fh_len = r.opaque(fh_out, 64);
    return r.ok() ? 0 : -1;
}

int liz_nfs_lookup(liz_nfs_t* h, const uint8_t* dirfh, uint32_t dlen,
                   const char* name, uint8_t* fh_out, uint32_t* fh_len) {
    std::lock_guard<std::mutex> g(h->mu);
    XdrW args;
    args.opaque(dirfh, dlen).str(name);
    if (!h->call(kProgNfs, 3, kNfsLookup, args.bytes())) return -1;
    return nfs_fh_result(h, fh_out, fh_len, false);
}

int liz_nfs_create(liz_nfs_t* h, const uint8_t* dirfh, uint32_t dlen,
                   const char* name, uint8_t* fh_out, uint32_t* fh_len) {
    std::lock_guard<std::mutex> g(h->mu);
    XdrW args;
    args.opaque(dirfh, dlen).str(name);
    args.u32(0);  // how = UNCHECKED + sattr3
    args.u32(1).u32(0644);  // mode set
    args.u32(0).u32(0).u32(0);  // uid/gid/size unset
    args.u32(0).u32(0);  // atime/mtime: don't change
    if (!h->call(kProgNfs, 3, kNfsCreate, args.bytes())) return -1;
    return nfs_fh_result(h, fh_out, fh_len, true);
}

int64_t liz_nfs_write(liz_nfs_t* h, const uint8_t* fh, uint32_t fhlen,
                      uint64_t offset, uint32_t count, const uint8_t* buf,
                      int stable) {
    std::lock_guard<std::mutex> g(h->mu);
    XdrW args;
    args.opaque(fh, fhlen).u64(offset).u32(count).u32(
        static_cast<uint32_t>(stable));
    args.opaque(buf, count);
    if (!h->call(kProgNfs, 3, kNfsWrite, args.bytes())) return -1;
    size_t off = h->results_off;
    XdrR r(h->reply.data() + off, h->reply.size() - off);
    uint32_t status = r.u32();
    r.skip_wcc_data();
    if (status != 0) return -static_cast<int64_t>(status);
    uint32_t written = r.u32();
    return r.ok() ? static_cast<int64_t>(written) : -1;
}

int64_t liz_nfs_read(liz_nfs_t* h, const uint8_t* fh, uint32_t fhlen,
                     uint64_t offset, uint32_t count, uint8_t* buf) {
    std::lock_guard<std::mutex> g(h->mu);
    XdrW args;
    args.opaque(fh, fhlen).u64(offset).u32(count);
    if (!h->call(kProgNfs, 3, kNfsRead, args.bytes())) return -1;
    size_t off = h->results_off;
    XdrR r(h->reply.data() + off, h->reply.size() - off);
    uint32_t status = r.u32();
    r.skip_post_op_attr();
    if (status != 0) return -static_cast<int64_t>(status);
    r.u32();  // count (the opaque length is authoritative)
    r.u32();  // eof
    uint32_t got = r.opaque(buf, count);
    return r.ok() ? static_cast<int64_t>(got) : -1;
}

int liz_nfs_commit(liz_nfs_t* h, const uint8_t* fh, uint32_t fhlen) {
    std::lock_guard<std::mutex> g(h->mu);
    XdrW args;
    args.opaque(fh, fhlen).u64(0).u32(0);
    if (!h->call(kProgNfs, 3, kNfsCommit, args.bytes())) return -1;
    size_t off = h->results_off;
    XdrR r(h->reply.data() + off, h->reply.size() - off);
    uint32_t status = r.u32();
    return status == 0 ? 0 : static_cast<int>(status);
}

}  // extern "C"
