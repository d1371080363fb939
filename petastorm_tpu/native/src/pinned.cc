// DMA-friendly host memory for the arena pool.
//
// pst_pinned_alloc maps page-aligned anonymous memory (MAP_POPULATE
// pre-faults every page so first-touch faults never land inside the
// assemble thread) and best-effort mlocks it so the pages stay resident
// for the accelerator runtime's DMA engine. mlock failure (RLIMIT_MEMLOCK)
// is not an error: the mapping is still page-aligned and pre-faulted,
// which is most of the win on hosts without CAP_IPC_LOCK.

#include <cstddef>

#include <sys/mman.h>

extern "C" {

// Returns 1 when the region is mlocked, 0 when page-aligned only,
// -1 when the mapping itself failed. *out receives the base pointer.
int pst_pinned_alloc(size_t nbytes, int do_lock, void** out) {
    if (out == nullptr || nbytes == 0) return -1;
    int flags = MAP_PRIVATE | MAP_ANONYMOUS;
#ifdef MAP_POPULATE
    flags |= MAP_POPULATE;
#endif
    void* p = mmap(nullptr, nbytes, PROT_READ | PROT_WRITE, flags, -1, 0);
    if (p == MAP_FAILED) return -1;
    int locked = 0;
    if (do_lock && mlock(p, nbytes) == 0) locked = 1;
    *out = p;
    return locked;
}

void pst_pinned_free(void* p, size_t nbytes, int locked) {
    if (p == nullptr) return;
    if (locked) munlock(p, nbytes);
    munmap(p, nbytes);
}

}  // extern "C"
