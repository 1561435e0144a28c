// dlti_tpu native runtime: KV block allocator core.
//
// The reference outsources all native runtime code to external wheels
// (torch/NCCL/DeepSpeed ops — SURVEY.md §2b); this is the in-tree TPU-side
// equivalent for the serving engine's hot host path: block allocation runs
// between every decode step, so it must never contend with Python object
// churn. Exposed through a C ABI consumed via ctypes
// (dlti_tpu/utils/native.py); contract tested against the pure-Python
// fallback in tests/test_serving.py.
//
// Block 0 is reserved as the trash block (inactive decode slots write
// there); the allocator never hands it out.

#include <cstdint>
#include <mutex>
#include <vector>

namespace {

struct Allocator {
  std::mutex mu;
  std::vector<int32_t> free_list;  // LIFO for cache locality
  std::vector<uint8_t> live;       // live[b]: handed out, not yet freed
  int32_t num_blocks;
};

}  // namespace

extern "C" {

void* dlti_allocator_create(int32_t num_blocks) {
  if (num_blocks < 2) return nullptr;
  auto* a = new Allocator();
  a->num_blocks = num_blocks;
  a->live.assign(num_blocks, 0);
  a->free_list.reserve(num_blocks - 1);
  // Matches the Python fallback: pop() yields ascending block ids first.
  for (int32_t b = num_blocks - 1; b >= 1; --b) a->free_list.push_back(b);
  return a;
}

void dlti_allocator_destroy(void* handle) {
  delete static_cast<Allocator*>(handle);
}

int32_t dlti_allocator_num_free(void* handle) {
  auto* a = static_cast<Allocator*>(handle);
  std::lock_guard<std::mutex> lock(a->mu);
  return static_cast<int32_t>(a->free_list.size());
}

// All-or-nothing: returns 1 and fills `out[n]` on success, 0 otherwise.
int32_t dlti_allocator_allocate(void* handle, int32_t n, int32_t* out) {
  auto* a = static_cast<Allocator*>(handle);
  std::lock_guard<std::mutex> lock(a->mu);
  if (n < 0 || static_cast<size_t>(n) > a->free_list.size()) return 0;
  for (int32_t i = 0; i < n; ++i) {
    out[i] = a->free_list.back();
    a->free_list.pop_back();
    a->live[out[i]] = 1;
  }
  return 1;
}

// Guarded free: O(1) live-flag check per block. Returns 1 and frees the
// whole batch, or returns 0 and frees NOTHING if any id is out of range,
// not currently allocated (double free), or duplicated within the batch —
// mirroring the Python free-list guard: a silent double free would hand
// one block to two sequences and corrupt their KV far from the cause.
int32_t dlti_allocator_free_checked(void* handle, int32_t n,
                                    const int32_t* blocks) {
  auto* a = static_cast<Allocator*>(handle);
  std::lock_guard<std::mutex> lock(a->mu);
  for (int32_t i = 0; i < n; ++i) {
    int32_t b = blocks[i];
    if (b < 1 || b >= a->num_blocks || !a->live[b]) {
      for (int32_t j = 0; j < i; ++j) a->live[blocks[j]] = 1;  // roll back
      return 0;
    }
    a->live[b] = 0;  // also catches duplicates within this batch
  }
  for (int32_t i = 0; i < n; ++i) a->free_list.push_back(blocks[i]);
  return 1;
}

}  // extern "C"
