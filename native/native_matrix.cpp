// Full-matrix sanitizer harness over the native data plane.
//
// The shm-only stress loop (shm_stress.cpp) was ISSUE-6's acceptance
// target; this harness promotes the sanitizer builds to the FULL
// native client/server surface so `make sanitize` exercises, under
// ASan+UBSan and TSan:
//
//   * the GF(2^8) table math: lz_ec_encode single- vs multi-threaded
//     on 64-byte-unaligned lengths (the mt slice split), scalar and
//     SIMD dispatch — byte-identity checked between the two paths
//     (cross-checked against ops/gf256.py by tests/test_native.py);
//   * CRC32 on unaligned pointers and odd lengths (the hand-rolled
//     8-byte slicing + pclmul stitch);
//   * stripe scatter/gather round trips with partial tail blocks
//     (the offset arithmetic the UBSan sweep targets);
//   * the serve_native write path: WriteInit / bulk write / vectored
//     scatterv multi-part writes with deferred ack collection /
//     WriteEnd sealing, from concurrent client threads;
//   * the serve_native read path: lz_read_part, lz_read_part_bulk and
//     the striped lz_read_parts_gather reassembly, plus version-
//     mismatch and out-of-bounds error paths, under a concurrent
//     read storm (thread-per-connection and proactor paths).
//
// The NFS C client (client_native.cpp) needs a live gateway, so its
// sanitizer leg runs from Python: `make -C native sanitize` is wrapped
// by the top-level `make sanitize`, which LD_PRELOADs the ASan build
// under the tests/test_nfs.py C-client round trip.
//
// Exit 0 = every checked exchange behaved; sanitizers report findings
// on stderr and (with halt_on_error / -fno-sanitize-recover) fail the
// run.

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <random>
#include <string>
#include <thread>
#include <vector>

#include "wire.h"

extern "C" {
uint32_t lz_crc32(uint32_t crc, const uint8_t* data, size_t len);
void lz_crc32_blocks(const uint8_t* data, size_t nblocks, size_t block_size,
                     uint32_t* out);
void lz_ec_encode(size_t len, int k, int rows, const uint8_t* matrix,
                  const uint8_t* const* src, uint8_t* const* dst);
void lz_ec_encode_mt(size_t len, int k, int rows, const uint8_t* matrix,
                     const uint8_t* const* src, uint8_t* const* dst,
                     int nthreads);
void lz_stripe_scatter(const uint8_t* data, uint64_t nbytes, uint32_t d,
                       uint32_t blocks_per_part, uint8_t* out);
void lz_stripe_gather(const uint8_t* const* parts, uint32_t d,
                      uint64_t nbytes, uint8_t* out);
int lz_serve_start(const char* folders_nl, const char* host, int port);
int lz_serve_port(int handle);
void lz_serve_stop(int handle);
int lz_write_part_bulk(int fd, uint64_t chunk_id, const uint8_t* payload,
                       uint64_t len, uint64_t part_offset, uint32_t write_id);
int lz_read_part(int fd, uint64_t chunk_id, uint32_t version,
                 uint32_t part_id, uint32_t offset, uint32_t size,
                 uint8_t* out);
int lz_read_part_bulk(int fd, uint64_t chunk_id, uint32_t version,
                      uint32_t part_id, uint32_t offset, uint32_t size,
                      uint8_t* out);
struct lz_part_req {
    int fd;
    uint64_t chunk_id;
    uint32_t version;
    uint32_t part_id;
    int32_t rc;
};
int lz_write_parts_scatterv(lz_part_req* parts, uint32_t n,
                            const uint8_t* const* payloads,
                            const uint64_t* lens, uint64_t part_offset,
                            uint32_t max_ms, uint32_t flags);
int lz_write_collect_acks(lz_part_req* parts, uint32_t n, uint32_t max_ms);
int lz_write_parts_exchange(lz_part_req* parts, uint32_t n,
                            const uint8_t* const* init_frames,
                            const uint32_t* init_lens,
                            const uint8_t* const* payloads,
                            const uint64_t* lens, uint64_t part_offset,
                            const uint8_t* const* end_frames,
                            const uint32_t* end_lens, uint32_t max_ms,
                            uint64_t* leg_us);
int lz_read_parts_gather(lz_part_req* parts, uint32_t d, uint32_t offset,
                         uint32_t region_blocks, uint8_t* out,
                         uint32_t max_ms);
int lz_read_parts_wave(lz_part_req* parts, uint32_t n,
                       const uint32_t* offsets, const uint32_t* sizes,
                       uint8_t* const* dsts, uint32_t max_ms,
                       uint64_t* done_us);
}

namespace {

constexpr uint32_t kBlock = 64 * 1024;
constexpr uint32_t kScatterNoAck = 1;
constexpr int32_t kWavePending = 1 << 30;

std::atomic<int> g_failures{0};

void fail(const char* what) {
    std::fprintf(stderr, "native_matrix: FAIL: %s\n", what);
    g_failures.fetch_add(1);
}

void fill_pattern(std::vector<uint8_t>& buf, uint32_t seed) {
    std::mt19937 rng(seed);
    for (auto& b : buf) b = static_cast<uint8_t>(rng());
}

// ---- GF(2^8) / EC ---------------------------------------------------------

void gf_leg() {
    // unaligned length: exercises the mt ceil-divide + 64-byte slice
    // alignment and the SIMD tail handling
    const size_t len = (1u << 20) + 13;
    const int k = 8, rows = 4;
    std::vector<uint8_t> matrix(static_cast<size_t>(rows) * k);
    fill_pattern(matrix, 7);
    std::vector<std::vector<uint8_t>> src(k), dst_st(rows), dst_mt(rows);
    std::vector<const uint8_t*> sp(k);
    std::vector<uint8_t*> dp_st(rows), dp_mt(rows);
    for (int j = 0; j < k; ++j) {
        src[j].resize(len);
        fill_pattern(src[j], 100 + j);
        sp[j] = src[j].data();
    }
    for (int r = 0; r < rows; ++r) {
        dst_st[r].assign(len, 0xAA);
        dst_mt[r].assign(len, 0x55);
        dp_st[r] = dst_st[r].data();
        dp_mt[r] = dst_mt[r].data();
    }
    lz_ec_encode(len, k, rows, matrix.data(), sp.data(), dp_st.data());
    lz_ec_encode_mt(len, k, rows, matrix.data(), sp.data(), dp_mt.data(), 4);
    for (int r = 0; r < rows; ++r) {
        if (std::memcmp(dp_st[r], dp_mt[r], len) != 0)
            fail("ec encode mt != st (slice split corrupts parity)");
    }
    // small odd geometry through the scalar path
    const size_t small = 333;
    std::vector<uint8_t> m2 = {1, 2, 3, 4, 5, 6};  // rows=2, k=3
    std::vector<std::vector<uint8_t>> s2(3), d2(2);
    std::vector<const uint8_t*> s2p(3);
    std::vector<uint8_t*> d2p(2);
    for (int j = 0; j < 3; ++j) {
        s2[j].resize(small);
        fill_pattern(s2[j], 200 + j);
        s2p[j] = s2[j].data();
    }
    for (int r = 0; r < 2; ++r) {
        d2[r].assign(small, 0);
        d2p[r] = d2[r].data();
    }
    lz_ec_encode(small, 3, 2, m2.data(), s2p.data(), d2p.data());
}

// ---- CRC ------------------------------------------------------------------

void crc_leg() {
    std::vector<uint8_t> buf(kBlock * 3 + 31);
    fill_pattern(buf, 42);
    // unaligned start + odd length: the pre-alignment byte loop, the
    // 8-byte slices, and the tail all run
    uint32_t a = lz_crc32(0, buf.data() + 1, buf.size() - 5);
    // same bytes, split at an odd boundary: crc chaining must agree
    uint32_t b = lz_crc32(0, buf.data() + 1, 12345);
    b = lz_crc32(b, buf.data() + 1 + 12345, buf.size() - 5 - 12345);
    if (a != b) fail("crc32 split-chain mismatch");
    std::vector<uint32_t> crcs(3);
    lz_crc32_blocks(buf.data(), 3, kBlock, crcs.data());
    for (int i = 0; i < 3; ++i) {
        if (crcs[i] != lz_crc32(0, buf.data() + i * size_t{kBlock}, kBlock))
            fail("crc32_blocks != crc32");
    }
}

// ---- stripe scatter/gather ------------------------------------------------

void stripe_leg() {
    // 2.5-block tail: the partial-last-block 'covered' arithmetic
    const uint32_t d = 3, bpp = 2;
    const uint64_t nbytes = uint64_t{5} * kBlock + kBlock / 2;
    std::vector<uint8_t> data(nbytes);
    fill_pattern(data, 9);
    std::vector<uint8_t> parts(uint64_t{d} * bpp * kBlock, 0xEE);
    lz_stripe_scatter(data.data(), nbytes, d, bpp, parts.data());
    std::vector<const uint8_t*> pp(d);
    for (uint32_t p = 0; p < d; ++p)
        pp[p] = parts.data() + uint64_t{p} * bpp * kBlock;
    std::vector<uint8_t> back(nbytes, 0);
    lz_stripe_gather(pp.data(), d, nbytes, back.data());
    if (std::memcmp(back.data(), data.data(), nbytes) != 0)
        fail("stripe scatter/gather round trip");
}

// ---- serve: write + read paths -------------------------------------------

bool write_init(int sock, uint64_t chunk_id, uint32_t part_id) {
    lzwire::Msg msg(1210);
    msg.u32(1).u64(chunk_id).u32(1 /*version*/).u32(part_id)
        .u32(0 /*empty chain*/).u8(1 /*create*/);
    if (!msg.send(sock)) return false;
    std::vector<uint8_t> pay;
    uint32_t type = lzwire::recv_frame(sock, &pay, 1 << 16);
    return type == 1212 && pay.size() >= 18 && pay[17] == 0;
}

bool write_end(int sock, uint64_t chunk_id) {
    lzwire::Msg msg(1213);
    msg.u32(9).u64(chunk_id);
    if (!msg.send(sock)) return false;
    std::vector<uint8_t> pay;
    uint32_t type = lzwire::recv_frame(sock, &pay, 1 << 16);
    return type == 1212 && pay.size() >= 18 && pay[17] == 0;
}

// The frames the python caller hands lz_write_parts_exchange.
std::vector<uint8_t> init_frame(uint64_t chunk_id, uint32_t version,
                                uint32_t part_id) {
    return lzwire::Msg(1210).u32(1).u64(chunk_id).u32(version).u32(part_id)
        .u32(0 /*empty chain*/).u8(0 /*create=false: the part exists*/)
        .frame();
}

std::vector<uint8_t> end_frame(uint64_t chunk_id) {
    return lzwire::Msg(1213).u32(0).u64(chunk_id).frame();
}

// The one-shot exchange (init, bulk, end rounds in one call) over the
// connections a finished write session left open, as pooled sockets
// are: a rewrite of block 1 of every part, then a stale version that
// every server must refuse in leg 1 before a byte of data leaves.
void exchange_leg(lz_part_req* reqs, uint32_t d, uint64_t chunk_id,
                  const uint8_t* const* payloads, std::vector<uint8_t>& data,
                  uint64_t part_len) {
    std::vector<std::vector<uint8_t>> inits, ends;
    std::vector<const uint8_t*> ip(d), ep(d), pay(d);
    std::vector<uint32_t> il(d), el(d);
    std::vector<uint64_t> lens(d, kBlock);
    auto point = [&](uint32_t version) {
        inits.clear(); ends.clear();
        for (uint32_t p = 0; p < d; ++p) {
            inits.push_back(init_frame(chunk_id, version, p));
            ends.push_back(end_frame(chunk_id));
        }
        for (uint32_t p = 0; p < d; ++p) {
            ip[p] = inits[p].data(); il[p] = inits[p].size();
            ep[p] = ends[p].data(); el[p] = ends[p].size();
        }
    };
    // block 1 of part p takes block 0's bytes: chunk block d+p := block p
    for (uint32_t p = 0; p < d; ++p) pay[p] = payloads[p];
    uint64_t leg_us[3] = {7, 7, 7};
    point(1);
    for (uint32_t p = 0; p < d; ++p) reqs[p].rc = 0;
    int leg = lz_write_parts_exchange(reqs, d, ip.data(), il.data(),
                                      pay.data(), lens.data(), kBlock,
                                      ep.data(), el.data(), 10000, leg_us);
    if (leg != 0) fail("serve: exchange");
    if (!leg_us[0] || !leg_us[1] || !leg_us[2]) fail("serve: exchange legs");
    for (uint32_t p = 0; p < d; ++p) {
        if (reqs[p].rc != 0) fail("serve: exchange part rc");
        std::memcpy(data.data() + (uint64_t{d} + p) * kBlock,
                    data.data() + uint64_t{p} * kBlock, kBlock);
    }
    std::vector<uint8_t> whole(d * part_len, 0);
    if (lz_read_parts_gather(reqs, d, 0,
                             static_cast<uint32_t>(d * part_len / kBlock),
                             whole.data(), 10000) != 0)
        fail("serve: gather after exchange");
    else if (std::memcmp(whole.data(), data.data(), whole.size()) != 0)
        fail("serve: exchange bytes");
    point(99);
    leg = lz_write_parts_exchange(reqs, d, ip.data(), il.data(), pay.data(),
                                  lens.data(), kBlock, ep.data(), el.data(),
                                  10000, leg_us);
    if (leg != 1) fail("serve: stale-version exchange not refused at init");
    if (leg_us[1] != 0 || leg_us[2] != 0) fail("serve: a leg after a refusal");
    bool refused = false;
    for (uint32_t p = 0; p < d; ++p) refused |= reqs[p].rc > 0;
    if (!refused) fail("serve: exchange refusal carries no status");
    // misaligned offset: refused before a frame is sent
    point(1);
    leg = lz_write_parts_exchange(reqs, d, ip.data(), il.data(), pay.data(),
                                  lens.data(), 17, ep.data(), el.data(),
                                  10000, leg_us);
    if (leg != 2 || reqs[0].rc != -2) fail("serve: misaligned exchange");
    for (uint32_t p = 0; p < d; ++p) reqs[p].version = 1;
}

// One wave of a read plan in one call (lz_read_parts_wave): every part
// contiguous at its own place; an entry that is not pending left alone;
// a part that never answers reading -4 at the deadline while the others
// end OK; a misaligned part refused before a byte is sent; and, last
// (a refusal leaves its socket with an unread remainder), one part
// refused by its server while the others end OK. On connections of its
// own: a refused exchange leaves statuses unread on the shared ones.
void wave_leg(int port, uint32_t d, uint64_t chunk_id,
              const uint8_t* const* payloads, uint64_t part_len) {
    const uint32_t plen = static_cast<uint32_t>(part_len);
    std::vector<lz_part_req> reqs;
    for (uint32_t p = 0; p < d; ++p) {
        const int fd = lzwire::connect_data("127.0.0.1",
                                            static_cast<uint16_t>(port));
        if (fd < 0) fail("serve: wave connect");
        reqs.push_back(lz_part_req{fd, chunk_id, 1, p, 0});
    }
    std::vector<uint8_t> buf((d + 1) * part_len, 0xEE);
    std::vector<uint32_t> offs(d + 1, 0), sizes(d + 1, plen);
    std::vector<uint8_t*> dsts(d + 1);
    std::vector<uint64_t> us(d + 1, 0);
    for (uint32_t p = 0; p <= d; ++p) dsts[p] = buf.data() + p * part_len;
    auto pend = [&] { for (uint32_t p = 0; p < d; ++p) reqs[p].rc = kWavePending; };
    auto whole = [&](uint32_t p) {
        return std::memcmp(dsts[p], payloads[p], part_len) == 0;
    };
    pend();
    if (lz_read_parts_wave(reqs.data(), d, offs.data(), sizes.data(),
                           dsts.data(), 10000, us.data()) != 0)
        fail("serve: read_parts_wave");
    for (uint32_t p = 0; p < d; ++p) {
        if (reqs[p].rc != 0 || !us[p] || !whole(p))
            fail("serve: wave part");
    }
    // only part 1 pending, from its second block on, short of its end
    std::fill(buf.begin(), buf.end(), 0xEE);
    std::fill(us.begin(), us.end(), 0);
    offs[1] = kBlock;
    sizes[1] = plen - kBlock - 100;
    reqs[1].rc = kWavePending;
    if (lz_read_parts_wave(reqs.data(), d, offs.data(), sizes.data(),
                           dsts.data(), 10000, us.data()) != 0 ||
        std::memcmp(dsts[1], payloads[1] + kBlock, sizes[1]) != 0)
        fail("serve: wave subset");
    if (dsts[1][sizes[1]] != 0xEE || dsts[0][0] != 0xEE || us[0] || us[2])
        fail("serve: wave touched what was not pending");
    offs[1] = 0;
    sizes[1] = plen;
    // a peer that accepts and never answers, as part d
    int lsn = ::socket(AF_INET, SOCK_STREAM, 0);
    sockaddr_in sa{};
    sa.sin_family = AF_INET;
    sa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    socklen_t slen = sizeof(sa);
    int mute = ::socket(AF_INET, SOCK_STREAM, 0);
    if (::bind(lsn, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0 ||
        ::listen(lsn, 4) != 0 ||
        ::getsockname(lsn, reinterpret_cast<sockaddr*>(&sa), &slen) != 0 ||
        ::connect(mute, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0) {
        fail("serve: wave mute peer");
    } else {
        std::vector<lz_part_req> more(reqs);
        more.push_back(lz_part_req{mute, chunk_id, 1, 0, 0});
        for (auto& r : more) r.rc = kWavePending;
        if (lz_read_parts_wave(more.data(), d + 1, offs.data(), sizes.data(),
                               dsts.data(), 150, us.data()) == 0 ||
            more[d].rc != -4)
            fail("serve: wave deadline");
        for (uint32_t p = 0; p < d; ++p) {
            if (more[p].rc != 0 || !whole(p)) fail("serve: wave beside mute");
        }
    }
    ::close(mute);
    ::close(lsn);
    // misaligned: refused here, the socket untouched
    pend();
    offs[0] = 17;
    if (lz_read_parts_wave(reqs.data(), d, offs.data(), sizes.data(),
                           dsts.data(), 10000, us.data()) == 0 ||
        reqs[0].rc != -2 || reqs[1].rc != 0)
        fail("serve: wave misaligned");
    offs[0] = 0;
    // a stale version on part 1: its server's status, the others whole
    pend();
    reqs[1].version = 99;
    if (lz_read_parts_wave(reqs.data(), d, offs.data(), sizes.data(),
                           dsts.data(), 10000, us.data()) == 0 ||
        reqs[1].rc <= 0 || reqs[0].rc != 0 || reqs[2].rc != 0 || !whole(2))
        fail("serve: wave refusal");
    for (auto& r : reqs) {
        if (r.fd >= 0) ::close(r.fd);
    }
}

void serve_roundtrip(int port, uint64_t chunk_id, uint32_t seed) {
    const uint32_t d = 3, bpp = 2;
    const uint64_t part_len = uint64_t{bpp} * kBlock;
    std::vector<uint8_t> data(d * part_len);
    fill_pattern(data, seed);
    std::vector<uint8_t> parts(d * part_len);
    lz_stripe_scatter(data.data(), data.size(), d, bpp, parts.data());

    int socks[d];
    lz_part_req reqs[d];
    const uint8_t* payloads[d];
    uint64_t lens[d];
    bool ok = true;
    for (uint32_t p = 0; p < d; ++p) {
        socks[p] = lzwire::connect_data("127.0.0.1",
                                        static_cast<uint16_t>(port));
        if (socks[p] < 0 || !write_init(socks[p], chunk_id, p)) {
            fail("serve: connect/init");
            ok = false;
        }
        reqs[p] = lz_part_req{socks[p], chunk_id, 1, p, 0};
        payloads[p] = parts.data() + p * part_len;
        lens[p] = part_len;
    }
    if (ok) {
        // vectored scatterv with deferred acks (the windowed-client
        // shape), then the FIFO ack reap
        int rc = lz_write_parts_scatterv(reqs, d, payloads, lens, 0,
                                         10000, kScatterNoAck);
        if (rc != 0) fail("serve: scatterv send");
        rc = lz_write_collect_acks(reqs, d, 10000);
        if (rc != 0) fail("serve: scatterv acks");
        for (uint32_t p = 0; p < d; ++p) {
            if (reqs[p].rc != 0) fail("serve: scatterv part rc");
        }
        // a second, chunk-addressed bulk write over part 0 (1214 path)
        if (lz_write_part_bulk(socks[0], chunk_id, payloads[0], kBlock, 0,
                               77) != 0)
            fail("serve: bulk rewrite");
        for (uint32_t p = 0; p < d; ++p) {
            if (!write_end(socks[p], chunk_id)) fail("serve: write end");
        }
        // single-part read back, both framings
        std::vector<uint8_t> rd(part_len);
        if (lz_read_part(socks[1], chunk_id, 1, 1, 0,
                         static_cast<uint32_t>(part_len), rd.data()) != 0)
            fail("serve: read_part");
        else if (std::memcmp(rd.data(), payloads[1], part_len) != 0)
            fail("serve: read_part bytes");
        if (lz_read_part_bulk(socks[2], chunk_id, 1, 2, 0,
                              static_cast<uint32_t>(part_len),
                              rd.data()) != 0)
            fail("serve: read_part_bulk");
        else if (std::memcmp(rd.data(), payloads[2], part_len) != 0)
            fail("serve: read_part_bulk bytes");
        // striped gather read across all three connections
        std::vector<uint8_t> whole(d * part_len, 0);
        if (lz_read_parts_gather(reqs, d, 0, d * bpp, whole.data(),
                                 10000) != 0)
            fail("serve: read_parts_gather");
        else if (std::memcmp(whole.data(), data.data(), whole.size()) != 0)
            fail("serve: gather bytes");
        exchange_leg(reqs, d, chunk_id, payloads, data, part_len);
        // error paths: wrong version, out-of-bounds offset — must
        // return an error code, not touch bad memory
        if (lz_read_part(socks[0], chunk_id, 99, 0, 0, kBlock,
                         rd.data()) == 0)
            fail("serve: stale-version read accepted");
        if (lz_read_part(socks[0], chunk_id, 1, 0, 64u << 20, kBlock,
                         rd.data()) == 0)
            fail("serve: oob read accepted");
        // what the servers hold now (exchange_leg rewrote a block)
        lz_stripe_scatter(data.data(), data.size(), d, bpp, parts.data());
        wave_leg(port, d, chunk_id, payloads, part_len);
    }
    for (uint32_t p = 0; p < d; ++p) {
        if (socks[p] >= 0) ::close(socks[p]);
    }
}

}  // namespace

int main() {
    gf_leg();
    crc_leg();
    stripe_leg();

    char tmpl[] = "/tmp/lz_native_matrix_XXXXXX";
    if (::mkdtemp(tmpl) == nullptr) {
        std::perror("mkdtemp");
        return 2;
    }
    std::string folder(tmpl);
    int handle = lz_serve_start(folder.c_str(), "127.0.0.1", 0);
    if (handle < 0) {
        std::fprintf(stderr, "lz_serve_start failed\n");
        return 2;
    }
    int port = lz_serve_port(handle);

    // concurrent full write+read round trips: thread-per-connection
    // server paths under contention (TSan's main course)
    {
        std::vector<std::thread> threads;
        for (int t = 0; t < 4; ++t) {
            threads.emplace_back([port, t] {
                for (int round = 0; round < 3; ++round) {
                    serve_roundtrip(port,
                                    0x6100 + t * 16 + round,
                                    static_cast<uint32_t>(t * 31 + round));
                }
            });
        }
        for (auto& th : threads) th.join();
    }

    lz_serve_stop(handle);
    std::string rm = "rm -rf " + folder;
    if (std::system(rm.c_str()) != 0) { /* leave for tmpwatch */ }

    if (g_failures.load() != 0) {
        std::fprintf(stderr, "native_matrix: %d failures\n",
                     g_failures.load());
        return 1;
    }
    std::fprintf(stderr, "native_matrix: OK\n");
    return 0;
}
