// Native single-port gRPC+REST multiplexer: one epoll thread, zero
// per-connection threads.
//
// The reference multiplexes both protocols on one TCP port with cmux
// (reference internal/driver/daemon.go:87-159), riding Go's runtime
// poller. The Python fallback (keto_tpu/servers/mux.py) spends two pump
// threads per connection — parity-grade, not serving-grade. This is the
// serving-grade version: a front listener plus every splice runs on a
// single epoll loop with level-triggered interest masks, per-direction
// 64 KiB buffers, proxy flow control (a full buffer pauses reads from
// its producer — backpressure instead of unbounded memory), half-close
// propagation, a sniff deadline, and a connection cap.
//
// Protocol classification matches the Python mux: the first 4 bytes
// "PRI " (the HTTP/2 client preface, which gRPC always opens with) routes
// to the gRPC backend; anything else to the REST backend. The sniffed
// bytes are replayed to the backend before splicing.
//
// C ABI (ctypes-bound by keto_tpu/servers/native_mux.py):
//   mux_start(host, port, rest_port, grpc_port, max_conns) -> handle|0
//   mux_port(handle) -> bound front port
//   mux_stop(handle)

#include <arpa/inet.h>
#include <cerrno>
#include <cstdint>
#include <cstring>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <string>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <atomic>
#include <thread>
#include <time.h>
#include <unistd.h>
#include <unordered_map>
#include <vector>

namespace {

constexpr size_t BUF_CAP = 64 * 1024;
constexpr uint64_t SNIFF_DEADLINE_MS = 10'000;

uint64_t now_ms() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (uint64_t)ts.tv_sec * 1000 + ts.tv_nsec / 1'000'000;
}

struct Buf {
    char data[BUF_CAP];
    size_t off = 0, len = 0;  // pending bytes = [off, off+len)
    bool eof = false;         // producer half-closed after draining

    size_t space() const { return BUF_CAP - (off + len); }
    void compact() {
        if (off && len) memmove(data, data + off, len);
        if (off) off = 0;
    }
};

struct Conn {
    int client = -1;
    int backend = -1;
    bool doomed = false;  // close deferred to end of the epoll batch
    enum Phase { SNIFF, CONNECTING, SPLICE } phase = SNIFF;
    char head[4];
    size_t head_len = 0;
    uint64_t sniff_deadline = 0;
    Buf c2b;  // client → backend
    Buf b2c;  // backend → client
    bool c2b_shut = false;  // SHUT_WR delivered to backend
    bool b2c_shut = false;  // SHUT_WR delivered to client
};

struct Mux {
    int listener = -1;
    int ep = -1;
    int wake = -1;  // eventfd
    int front_port = 0;
    int rest_port, grpc_port;
    size_t max_conns;
    std::thread loop;
    std::atomic<bool> stopping{false};
    std::unordered_map<int, Conn*> by_fd;  // both client and backend fds
    std::vector<Conn*> doomed;             // closed after the event batch
    size_t live_conns = 0;

    void run();
    void accept_ready();
    void close_conn(Conn* c);
    void doom(Conn* c);
    void handle(Conn* c, uint32_t ev);
    void rearm(Conn* c);
    bool start_backend(Conn* c);
    void pump(int src, Buf& b, int dst, bool& shut_flag, Conn* c, bool& dead);
};

void arm(int ep, int fd, uint32_t events, int op) {
    epoll_event ev{};
    ev.events = events;
    ev.data.fd = fd;
    epoll_ctl(ep, op, fd, &ev);
}

void Mux::doom(Conn* c) {
    // fds stay registered (and un-reusable) until the batch ends, so a
    // stale event later in the same epoll_wait batch cannot hit a fresh
    // connection that reused the fd
    if (!c->doomed) {
        c->doomed = true;
        doomed.push_back(c);
    }
}

void Mux::close_conn(Conn* c) {
    if (live_conns) --live_conns;
    if (c->client >= 0) {
        epoll_ctl(ep, EPOLL_CTL_DEL, c->client, nullptr);
        by_fd.erase(c->client);
        close(c->client);
    }
    if (c->backend >= 0) {
        epoll_ctl(ep, EPOLL_CTL_DEL, c->backend, nullptr);
        by_fd.erase(c->backend);
        close(c->backend);
    }
    delete c;
}

void Mux::accept_ready() {
    for (;;) {
        int fd = accept4(listener, nullptr, nullptr, SOCK_NONBLOCK);
        if (fd < 0) return;
        if (live_conns >= max_conns) {
            close(fd);  // at capacity: shed
            continue;
        }
        int one = 1;
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        Conn* c = new Conn();
        ++live_conns;
        c->client = fd;
        c->sniff_deadline = now_ms() + SNIFF_DEADLINE_MS;
        by_fd[fd] = c;
        arm(ep, fd, EPOLLIN, EPOLL_CTL_ADD);
    }
}

bool Mux::start_backend(Conn* c) {
    int port = (c->head_len == 4 && memcmp(c->head, "PRI ", 4) == 0) ? grpc_port
                                                                     : rest_port;
    int fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) return false;
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    inet_pton(AF_INET, "127.0.0.1", &addr.sin_addr);
    if (connect(fd, (sockaddr*)&addr, sizeof(addr)) < 0 && errno != EINPROGRESS) {
        close(fd);
        return false;
    }
    c->backend = fd;
    c->phase = Conn::CONNECTING;
    // the sniffed head replays through the c2b buffer once connected
    memcpy(c->c2b.data, c->head, c->head_len);
    c->c2b.len = c->head_len;
    by_fd[fd] = c;
    arm(ep, fd, EPOLLOUT, EPOLL_CTL_ADD);
    return true;
}

// one direction: read from src into b (if space), flush b into dst;
// half-close dst once the producer reached EOF and the buffer drained
void Mux::pump(int src, Buf& b, int dst, bool& shut_flag, Conn*, bool& dead) {
    if (!b.eof && src >= 0) {
        b.compact();
        while (b.space()) {
            ssize_t n = recv(src, b.data + b.off + b.len, b.space(), 0);
            if (n > 0) {
                b.len += (size_t)n;
                continue;
            }
            if (n == 0) {
                b.eof = true;
            } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
            } else {
                dead = true;
            }
            break;
        }
    }
    while (b.len && dst >= 0) {
        ssize_t n = send(dst, b.data + b.off, b.len, MSG_NOSIGNAL);
        if (n > 0) {
            b.off += (size_t)n;
            b.len -= (size_t)n;
            if (!b.len) b.off = 0;
            continue;
        }
        if (errno == EAGAIN || errno == EWOULDBLOCK) break;
        dead = true;
        break;
    }
    if (b.eof && !b.len && !shut_flag && dst >= 0) {
        shutdown(dst, SHUT_WR);
        shut_flag = true;
    }
}

void Mux::rearm(Conn* c) {
    // level-triggered interest recomputed from buffer state — a full
    // buffer drops EPOLLIN on its producer: proxy flow control
    uint32_t cli = 0, be = 0;
    if (!c->c2b.eof && c->c2b.space()) cli |= EPOLLIN;
    if (c->b2c.len) cli |= EPOLLOUT;
    if (!c->b2c.eof && c->b2c.space()) be |= EPOLLIN;
    if (c->c2b.len) be |= EPOLLOUT;
    arm(ep, c->client, cli, EPOLL_CTL_MOD);
    arm(ep, c->backend, be, EPOLL_CTL_MOD);
}

void Mux::handle(Conn* c, uint32_t ev) {
    if (c->doomed) return;  // stale event within this batch
    if (c->phase == Conn::SNIFF) {
        if (ev & (EPOLLHUP | EPOLLERR)) {
            doom(c);
            return;
        }
        ssize_t n = recv(c->client, c->head + c->head_len, 4 - c->head_len, 0);
        if (n <= 0) {
            if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) return;
            doom(c);
            return;
        }
        c->head_len += (size_t)n;
        if (c->head_len < 4) return;
        epoll_ctl(ep, EPOLL_CTL_DEL, c->client, nullptr);
        if (!start_backend(c)) {
            doom(c);
        }
        return;
    }
    if (c->phase == Conn::CONNECTING) {
        int err = 0;
        socklen_t len = sizeof(err);
        getsockopt(c->backend, SOL_SOCKET, SO_ERROR, &err, &len);
        if ((ev & (EPOLLHUP | EPOLLERR)) || err) {
            doom(c);
            return;
        }
        c->phase = Conn::SPLICE;
        arm(ep, c->client, EPOLLIN, EPOLL_CTL_ADD);
        // fall through to splice below to flush the replayed head
        ev = EPOLLOUT;
    }
    bool dead = (ev & (EPOLLERR)) != 0;
    // run both directions regardless of which fd fired — level-triggered
    // interest masks keep this cheap and correct
    if (!dead) {
        pump(c->client, c->c2b, c->backend, c->c2b_shut, c, dead);
        pump(c->backend, c->b2c, c->client, c->b2c_shut, c, dead);
    }
    if (dead || (c->c2b_shut && c->b2c_shut)) {
        doom(c);
        return;
    }
    rearm(c);
}

void Mux::run() {
    epoll_event evs[256];
    for (;;) {
        int n = epoll_wait(ep, evs, 256, 250);
        if (stopping.load()) return;
        if (n < 0) {
            if (errno == EINTR) continue;
            return;
        }
        for (int i = 0; i < n; ++i) {
            int fd = evs[i].data.fd;
            if (fd == listener) {
                accept_ready();
                continue;
            }
            if (fd == wake) return;
            auto it = by_fd.find(fd);
            if (it == by_fd.end()) continue;
            handle(it->second, evs[i].events);
        }
        // sniff-deadline sweep (rare path; map is small at rest)
        uint64_t t = now_ms();
        for (auto& [fd, c] : by_fd)
            if (c->phase == Conn::SNIFF && t > c->sniff_deadline) doom(c);
        for (Conn* c : doomed) close_conn(c);
        doomed.clear();
    }
}

}  // namespace

extern "C" {

Mux* mux_start(const char* host, int port, int rest_port, int grpc_port,
               int max_conns) {
    Mux* m = new Mux();
    m->rest_port = rest_port;
    m->grpc_port = grpc_port;
    m->max_conns = max_conns > 0 ? (size_t)max_conns : 4096;
    m->listener = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (m->listener < 0) {
        delete m;
        return nullptr;
    }
    int one = 1;
    setsockopt(m->listener, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    if (!host || !*host || strcmp(host, "0.0.0.0") == 0) {
        addr.sin_addr.s_addr = INADDR_ANY;
    } else if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
        close(m->listener);
        delete m;
        return nullptr;
    }
    if (bind(m->listener, (sockaddr*)&addr, sizeof(addr)) < 0 ||
        listen(m->listener, 1024) < 0) {
        close(m->listener);
        delete m;
        return nullptr;
    }
    sockaddr_in bound{};
    socklen_t blen = sizeof(bound);
    getsockname(m->listener, (sockaddr*)&bound, &blen);
    m->front_port = ntohs(bound.sin_port);

    m->ep = epoll_create1(0);
    m->wake = eventfd(0, EFD_NONBLOCK);
    if (m->ep < 0 || m->wake < 0) {
        if (m->ep >= 0) close(m->ep);
        if (m->wake >= 0) close(m->wake);
        close(m->listener);
        delete m;
        return nullptr;
    }
    arm(m->ep, m->listener, EPOLLIN, EPOLL_CTL_ADD);
    arm(m->ep, m->wake, EPOLLIN, EPOLL_CTL_ADD);
    m->loop = std::thread([m] { m->run(); });
    return m;
}

int mux_port(const Mux* m) { return m->front_port; }

void mux_stop(Mux* m) {
    m->stopping.store(true);
    uint64_t one = 1;
    ssize_t ignored = write(m->wake, &one, sizeof(one));
    (void)ignored;
    if (m->loop.joinable()) m->loop.join();
    // The drain's last step. The loop has gone, perhaps with events in
    // hand: a response the backend has written (the REST drain waits for
    // exactly that) but the loop has not relayed yet must still reach its
    // client before the connection is closed: the zero-dropped-requests
    // half of a rolling restart. Backend -> client only, bounded.
    uint64_t until = now_ms() + 500;
    for (;;) {
        bool pending = false;
        for (auto& [fd, c] : m->by_fd) {
            if (fd != c->client || c->phase != Conn::SPLICE || c->doomed) continue;
            for (bool dead = false;;) {
                m->pump(c->backend, c->b2c, c->client, c->b2c_shut, c, dead);
                if (dead) break;
                if (c->b2c.len) {
                    pending = true;  // the client's socket is full: come back
                    break;
                }
                char more;
                if (c->b2c.eof || recv(c->backend, &more, 1, MSG_PEEK | MSG_DONTWAIT) <= 0) break;
            }
        }
        if (!pending || now_ms() > until) break;
        usleep(1000);
    }
    std::vector<Conn*> conns;
    for (auto& [fd, c] : m->by_fd)
        if (fd == c->client) conns.push_back(c);
    for (Conn* c : conns) m->close_conn(c);
    close(m->listener);
    close(m->ep);
    close(m->wake);
    delete m;
}

}  // extern "C"
