// Subprocess execution with wall-clock timeout and process-group kill.
//
// Same behavior as the reference executor's run path (server.rs:149-169):
// run the interpreter on the script with the request env merged in, capture
// stdout/stderr, and on timeout return exit_code -1 with stderr "Execution
// timed out". The child gets its own process group (setpgid) so the timeout
// kill also reaps grandchildren the user code spawned.
#pragma once

#include <fcntl.h>
#include <poll.h>
#include <signal.h>
#include <spawn.h>
#include <sys/wait.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstring>
#include <functional>
#include <map>
#include <string>
#include <thread>
#include <vector>

namespace subprocess {

struct RunResult {
  std::string out;
  std::string err;
  int exit_code = 0;
  bool timed_out = false;
};

inline constexpr const char* kTimeoutMessage = "Execution timed out";

// A spawned child with captured output (and optionally writable stdin).
// Returned by spawn(); pass to collect() to stream output until exit.
struct Child {
  pid_t pid = -1;
  int stdin_fd = -1;   // -1 unless want_stdin
  int status_fd = -1;  // -1 unless want_status (child writes on its fd 3)
  int out_fd = -1;
  int err_fd = -1;

  bool valid() const { return pid > 0; }

  bool alive() const {
    if (pid <= 0) return false;
    int status = 0;
    return waitpid(pid, &status, WNOHANG) == 0;
  }

  void close_fds() {
    if (stdin_fd >= 0) { close(stdin_fd); stdin_fd = -1; }
    if (status_fd >= 0) { close(status_fd); status_fd = -1; }
    if (out_fd >= 0) { close(out_fd); out_fd = -1; }
    if (err_fd >= 0) { close(err_fd); err_fd = -1; }
  }

  void kill_group() {
    if (pid > 0) kill(-pid, SIGKILL);
  }
};

// Block up to timeout_s for one byte on a status fd. True iff a byte arrived;
// false on EOF (writer died without reporting) or deadline.
// Waits for a specific status byte on the pipe, skipping earlier protocol
// bytes (the warm worker writes 'P' at preload-done, then 'S' right before
// user code runs; a caller waiting for 'S' must tolerate an unconsumed 'P').
// expected == 0 accepts any byte. Skipped bytes are appended to *skipped when
// given (the preload's failure records travel ahead of 'S').
inline bool wait_for_status_byte(int fd, double timeout_s, char expected = 0,
                                 std::string* skipped = nullptr) {
  if (fd < 0) return false;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::duration<double>(timeout_s);
  while (true) {
    auto remaining = std::chrono::duration_cast<std::chrono::milliseconds>(
                         deadline - std::chrono::steady_clock::now())
                         .count();
    pollfd p{fd, POLLIN, 0};
    int rc = poll(&p, 1,
                  static_cast<int>(std::clamp<long long>(remaining, 0, 1000)));
    if (rc < 0) return false;
    if (p.revents & (POLLIN | POLLHUP)) {
      char b;
      ssize_t n = read(fd, &b, 1);
      if (n == 1) {
        if (expected == 0 || b == expected) return true;
        if (skipped) skipped->push_back(b);
        continue;  // earlier protocol byte; keep draining
      }
      if (n == 0) return false;  // EOF: writer exited silently
      if (errno != EAGAIN && errno != EINTR) return false;
    }
    if (remaining <= 0) return false;
  }
}

// Fork+exec into its own process group with stdout/stderr pipes (and stdin /
// status pipes when requested). env is the COMPLETE child environment.
inline Child spawn(const std::vector<std::string>& argv,
                   const std::map<std::string, std::string>& env,
                   const std::string& cwd,
                   bool want_stdin = false,
                   bool want_status = false) {
  int out_pipe[2] = {-1, -1}, err_pipe[2] = {-1, -1}, in_pipe[2] = {-1, -1},
      status_pipe[2] = {-1, -1};
  auto close_all = [&] {
    for (int fd : {out_pipe[0], out_pipe[1], err_pipe[0], err_pipe[1],
                   in_pipe[0], in_pipe[1], status_pipe[0], status_pipe[1]})
      if (fd >= 0) close(fd);
  };
  if (pipe(out_pipe) != 0 || pipe(err_pipe) != 0 ||
      (want_stdin && pipe(in_pipe) != 0) ||
      (want_status && pipe(status_pipe) != 0)) {
    close_all();
    return {};
  }

  pid_t pid = fork();
  if (pid < 0) {
    close_all();
    return {};
  }
  if (pid == 0) {
    // child
    setpgid(0, 0);
    if (!cwd.empty()) {
      if (chdir(cwd.c_str()) != 0) _exit(127);
    }
    if (want_stdin) {
      dup2(in_pipe[0], STDIN_FILENO);
      close(in_pipe[0]); close(in_pipe[1]);
    }
    dup2(out_pipe[1], STDOUT_FILENO);
    dup2(err_pipe[1], STDERR_FILENO);
    close(out_pipe[0]); close(out_pipe[1]);
    close(err_pipe[0]); close(err_pipe[1]);
    if (want_status) {
      // AFTER the other pipes are dup2'd+closed: fd 3 may have been one of
      // their descriptor numbers, and closing them would clobber it.
      dup2(status_pipe[1], 3);
      if (status_pipe[0] != 3) close(status_pipe[0]);
      if (status_pipe[1] != 3) close(status_pipe[1]);
    }
    std::vector<char*> cargv;
    for (const auto& a : argv) cargv.push_back(const_cast<char*>(a.c_str()));
    cargv.push_back(nullptr);
    std::vector<std::string> env_strings;
    env_strings.reserve(env.size());
    for (const auto& [k, v] : env) env_strings.push_back(k + "=" + v);
    std::vector<char*> cenv;
    for (const auto& e : env_strings) cenv.push_back(const_cast<char*>(e.c_str()));
    cenv.push_back(nullptr);
    execve(argv[0].c_str(), cargv.data(), cenv.data());
    // fallback to PATH lookup
    execvpe(argv[0].c_str(), cargv.data(), cenv.data());
    fprintf(stderr, "exec failed: %s\n", strerror(errno));
    _exit(127);
  }

  // parent
  setpgid(pid, pid);  // race-safe double setpgid
  close(out_pipe[1]);
  close(err_pipe[1]);
  Child child;
  child.pid = pid;
  child.out_fd = out_pipe[0];
  child.err_fd = err_pipe[0];
  if (want_stdin) {
    close(in_pipe[0]);
    child.stdin_fd = in_pipe[1];
  }
  if (want_status) {
    close(status_pipe[1]);
    child.status_fd = status_pipe[0];
    fcntl(child.status_fd, F_SETFL, O_NONBLOCK);
  }
  fcntl(child.out_fd, F_SETFL, O_NONBLOCK);
  fcntl(child.err_fd, F_SETFL, O_NONBLOCK);
  return child;
}

// Stream the child's output until exit or deadline (timeout → process-group
// SIGKILL, exit_code -1, stderr replaced with the timeout message).
inline RunResult collect(Child child, double timeout_s) {
  if (!child.valid()) return {"", "spawn failed", -1, false};
  if (child.stdin_fd >= 0) { close(child.stdin_fd); child.stdin_fd = -1; }
  if (child.status_fd >= 0) { close(child.status_fd); child.status_fd = -1; }
  int out_pipe0 = child.out_fd, err_pipe0 = child.err_fd;
  pid_t pid = child.pid;

  RunResult result;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::duration<double>(timeout_s);
  bool out_open = true, err_open = true;
  char buf[1 << 16];
  while (out_open || err_open) {
    auto remaining = std::chrono::duration_cast<std::chrono::milliseconds>(
                         deadline - std::chrono::steady_clock::now())
                         .count();
    if (remaining <= 0) {
      result.timed_out = true;
      kill(-pid, SIGKILL);
      break;
    }
    pollfd fds[2];
    nfds_t nfds = 0;
    if (out_open) fds[nfds++] = {out_pipe0, POLLIN, 0};
    if (err_open) fds[nfds++] = {err_pipe0, POLLIN, 0};
    int rc = poll(fds, nfds, static_cast<int>(std::min<long long>(remaining, 1000)));
    if (rc < 0) break;
    for (nfds_t i = 0; i < nfds; ++i) {
      if (!(fds[i].revents & (POLLIN | POLLHUP))) continue;
      ssize_t n = read(fds[i].fd, buf, sizeof buf);
      bool is_out = fds[i].fd == out_pipe0;
      if (n > 0) {
        (is_out ? result.out : result.err).append(buf, static_cast<size_t>(n));
      } else if (n == 0 || (n < 0 && errno != EAGAIN)) {
        if (is_out) out_open = false; else err_open = false;
      }
    }
  }
  close(out_pipe0);
  close(err_pipe0);

  int status = 0;
  waitpid(pid, &status, 0);
  if (result.timed_out) {
    result.out.clear();
    result.err = kTimeoutMessage;
    result.exit_code = -1;
  } else if (WIFEXITED(status)) {
    result.exit_code = WEXITSTATUS(status);
  } else if (WIFSIGNALED(status)) {
    result.exit_code = -WTERMSIG(status);
  }
  return result;
}

// Warm-worker collect: the bootstrap reports the script's exit code on the
// status pipe ("X<code>\n") and closes its stdio as soon as user code and
// user atexit handlers finish, so the response doesn't wait out interpreter
// finalization (~100 ms with a scientific stack loaded — measured as the
// whole warm-path latency floor). The zombie is reaped on a detached thread.
// Falls back to a blocking reap when the worker dies without reporting
// (crash/signal/user closed fd 3). on_exit runs once the worker has been
// reaped, on whichever thread did it — the hook for work that must not
// overlap the worker's life (it may still hold the accelerator while it
// finalizes).
inline RunResult collect_warm(Child child, double timeout_s,
                              std::function<void()> on_exit = [] {}) {
  if (!child.valid()) {
    on_exit();
    return {"", "spawn failed", -1, false};
  }
  if (child.stdin_fd >= 0) { close(child.stdin_fd); child.stdin_fd = -1; }
  int out_pipe0 = child.out_fd, err_pipe0 = child.err_fd;
  pid_t pid = child.pid;

  RunResult result;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::duration<double>(timeout_s);
  bool out_open = true, err_open = true;
  char buf[1 << 16];
  while (out_open || err_open) {
    auto remaining = std::chrono::duration_cast<std::chrono::milliseconds>(
                         deadline - std::chrono::steady_clock::now())
                         .count();
    if (remaining <= 0) {
      result.timed_out = true;
      kill(-pid, SIGKILL);
      break;
    }
    pollfd fds[2];
    nfds_t nfds = 0;
    if (out_open) fds[nfds++] = {out_pipe0, POLLIN, 0};
    if (err_open) fds[nfds++] = {err_pipe0, POLLIN, 0};
    int rc = poll(fds, nfds, static_cast<int>(std::min<long long>(remaining, 1000)));
    if (rc < 0) break;
    for (nfds_t i = 0; i < nfds; ++i) {
      if (!(fds[i].revents & (POLLIN | POLLHUP))) continue;
      ssize_t n = read(fds[i].fd, buf, sizeof buf);
      bool is_out = fds[i].fd == out_pipe0;
      if (n > 0) {
        (is_out ? result.out : result.err).append(buf, static_cast<size_t>(n));
      } else if (n == 0 || (n < 0 && errno != EAGAIN)) {
        if (is_out) out_open = false; else err_open = false;
      }
    }
  }
  close(out_pipe0);
  close(err_pipe0);

  if (result.timed_out) {
    if (child.status_fd >= 0) { close(child.status_fd); child.status_fd = -1; }
    int status = 0;
    waitpid(pid, &status, 0);
    on_exit();
    result.out.clear();
    result.err = kTimeoutMessage;
    result.exit_code = -1;
    return result;
  }

  // Exit-code line ("X<code>\n") — normally already buffered when the pipes
  // EOF'd. Bounded by the REQUEST deadline, not a flat grace: user code that
  // closes its own stdio (both pipes EOF immediately) and keeps running must
  // still be limited by the execution timeout, and the fallback reap below
  // must never block on a live worker.
  std::string line;
  bool got_code = false;
  if (child.status_fd >= 0) {
    while (std::chrono::steady_clock::now() < deadline) {
      pollfd p{child.status_fd, POLLIN, 0};
      if (poll(&p, 1, 100) <= 0) continue;
      if (!(p.revents & (POLLIN | POLLHUP))) continue;
      char b;
      ssize_t n = read(child.status_fd, &b, 1);
      if (n <= 0) break;  // EOF: worker exited without reporting
      if (b == '\n') {
        got_code = !line.empty() && line[0] == 'X';
        break;
      }
      line.push_back(b);
    }
    close(child.status_fd);
    child.status_fd = -1;
  }
  if (got_code) {
    result.exit_code = atoi(line.c_str() + 1);
    std::thread([pid, on_exit] {
      int status = 0;
      waitpid(pid, &status, 0);
      on_exit();
    }).detach();
  } else {
    // No report: crashed worker (already dead — kill is a no-op) or stdio
    // closed by user code and the deadline elapsed (still running — kill
    // enforces the budget). Either way the reap below cannot block.
    const bool deadline_hit = std::chrono::steady_clock::now() >= deadline;
    kill(-pid, SIGKILL);
    int status = 0;
    waitpid(pid, &status, 0);
    on_exit();
    if (deadline_hit) {
      result.out.clear();
      result.err = kTimeoutMessage;
      result.exit_code = -1;
      result.timed_out = true;
    } else if (WIFEXITED(status)) {
      result.exit_code = WEXITSTATUS(status);
    } else if (WIFSIGNALED(status)) {
      result.exit_code = -WTERMSIG(status);
    }
  }
  return result;
}

// argv: program + args. env: complete child environment.
inline RunResult run(const std::vector<std::string>& argv,
                     const std::map<std::string, std::string>& env,
                     const std::string& cwd,
                     double timeout_s) {
  return collect(spawn(argv, env, cwd), timeout_s);
}

}  // namespace subprocess
