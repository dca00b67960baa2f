// Native runtime support library — the C++ side of the framework.
//
// The reference is pure C++ (SURVEY.md §2: "every component below is
// native"); its runtime pieces that are NOT the device compute path —
// aligned allocation (allreduce-mpi-sycl.cpp:19-21,154-159: ALIGNMENT
// 128 vs 2MB sycl::aligned_alloc), buffer init/validation kernels
// (Initialize :33-41, validation :192-204), ring-neighbor scheduling
// (SendRecvRing :43-59), and the timing statistics each app hand-rolls —
// are reimplemented here as a C library the Python layer binds with
// ctypes (no pybind11 in this image). The TPU compute path stays
// JAX/XLA/Pallas; this is the native harness around it.
//
// Build: make -C native   ->  native/libhpcpat.so

#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>

// The Makefile stamps the library with the hash of THIS file, and the
// loader (interop/native.py) looks for the hash of its checkout's
// source in the library's bytes before mapping it — a binary left over
// from other sources is never loaded.
#ifndef HPCPAT_SOURCE_ID
#define HPCPAT_SOURCE_ID "unstamped"
#endif

extern "C" {

const char* hp_source_id() { return HPCPAT_SOURCE_ID; }

// ---- timing statistics engine (≙ the min-of-reps protocol every app
// hand-rolls, sycl_con.cpp:101-119) ------------------------------------

// out[0]=min, out[1]=max, out[2]=mean, out[3]=stddev (population)
void hp_stats(const double* xs, int64_t n, double* out) {
  if (n <= 0) {
    out[0] = out[1] = out[2] = out[3] = 0.0;
    return;
  }
  double mn = xs[0], mx = xs[0], sum = 0.0;
  for (int64_t i = 0; i < n; ++i) {
    if (xs[i] < mn) mn = xs[i];
    if (xs[i] > mx) mx = xs[i];
    sum += xs[i];
  }
  double mean = sum / (double)n, var = 0.0;
  for (int64_t i = 0; i < n; ++i) {
    double d = xs[i] - mean;
    var += d * d;
  }
  out[0] = mn;
  out[1] = mx;
  out[2] = mean;
  out[3] = std::sqrt(var / (double)n);
}

// identity pass through native memory; lets Python verify the binding
// end-to-end (timing._native_identity round-trips samples through this)
void hp_roundtrip(const double* in, double* out, int64_t n) {
  std::memcpy(out, in, (size_t)n * sizeof(double));
}

// ---- aligned host allocator (≙ sycl::aligned_alloc with ALIGNMENT,
// allreduce-mpi-sycl.cpp:19-21; 2MB pages in allreduce-usm-...:16-18) ---

void* hp_aligned_alloc(size_t nbytes, size_t alignment) {
  if (alignment == 0 || (alignment & (alignment - 1)) != 0) return nullptr;
  if (nbytes == 0) nbytes = alignment;
  // round size up to a multiple of alignment (posix requirement)
  size_t rounded = (nbytes + alignment - 1) / alignment * alignment;
  void* p = nullptr;
  if (posix_memalign(&p, alignment < sizeof(void*) ? sizeof(void*) : alignment,
                     rounded) != 0)
    return nullptr;
  return p;
}

void hp_free(void* p) { std::free(p); }

// ---- buffer init + analytic validation (≙ Initialize kernel
// allreduce-mpi-sycl.cpp:33-41 and the elementwise oracle check
// :192-204) -------------------------------------------------------------

void hp_fill(float* p, int64_t n, float value) {
  for (int64_t i = 0; i < n; ++i) p[i] = value;
}

void hp_iota(float* p, int64_t n, float base, float step) {
  for (int64_t i = 0; i < n; ++i) p[i] = base + step * (float)i;
}

// returns index of first element with |p[i] - expected| > tol, or -1
int64_t hp_validate(const float* p, int64_t n, float expected, float tol) {
  for (int64_t i = 0; i < n; ++i)
    if (std::fabs(p[i] - expected) > tol) return i;
  return -1;
}

// ---- ring schedule (≙ the neighbor math of SendRecvRing,
// allreduce-mpi-sycl.cpp:43-59: right=(rank+1)%size, left=(rank-1+size)%size,
// with even/odd ordering for deadlock freedom) --------------------------

// writes size (src,dst) pairs for one ring step of `shift`
void hp_ring_plan(int32_t size, int32_t shift, int32_t* src, int32_t* dst) {
  for (int32_t r = 0; r < size; ++r) {
    src[r] = r;
    int32_t d = (r + shift) % size;
    if (d < 0) d += size;
    dst[r] = d;
  }
}

// the even/odd two-phase ordering of the reference (:50-58), exposed so
// tests can assert the deadlock-freedom property (every rank appears in
// exactly one send and one recv per phase)
// phase 0: even ranks send; phase 1: odd ranks send. Returns count.
int32_t hp_ring_phase(int32_t size, int32_t phase, int32_t* senders) {
  int32_t c = 0;
  for (int32_t r = phase; r < size; r += 2) senders[c++] = r;
  return c;
}

}  // extern "C"
