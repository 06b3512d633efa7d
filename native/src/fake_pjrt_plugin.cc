// A FAKE in-memory PJRT plugin (test-only): N virtual devices, byte-copy
// buffers, and an "executable" that echoes its inputs — just enough C API
// surface for pjrt_multidev_test to drive dllama::Client/Executable through
// the REAL dlopen -> Plugin_Initialize -> Client_Create -> per-device
// placement -> multi-device Execute path without any accelerator.
//
// Rationale: this container ships no multi-device PJRT plugin (libtpu.so
// needs TPU hardware; jaxlib's CPU client is not exported through the C
// API — see native/MULTIDEVICE.md). The fake makes
// the runtime's multi-device plumbing testable anywhere; the math of a real
// sharded program is validated by the driver's dryrun_multichip on virtual
// JAX devices and by single-chip native e2e on hardware.
//
// Not modeled (documented, deliberate): asynchrony (every event completes
// inline and is returned as nullptr, which the wrapper treats as ready),
// donation/aliasing, layouts, memories, errors-after-create.

#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "../third_party/pjrt_c_api.h"

// Opaque C-API types get concrete fake definitions here.
struct PJRT_Error {
  std::string message;
};

struct PJRT_Device {
  int id;
};

struct PJRT_Client {
  std::vector<PJRT_Device> devices;
  std::vector<PJRT_Device*> device_ptrs;
  std::string platform = "fake";
};

struct PJRT_Buffer {
  std::vector<unsigned char> data;
  std::vector<int64_t> dims;
  PJRT_Buffer_Type type;
  int device_id;
};

struct PJRT_Executable {
  size_t n_outputs;
};

struct PJRT_LoadedExecutable {
  PJRT_Client* client;
  size_t n_outputs;
};

namespace {

PJRT_Error* Err(const std::string& m) { return new PJRT_Error{m}; }

void ErrorMessage(PJRT_Error_Message_Args* a) {
  a->message = a->error->message.c_str();
  a->message_size = a->error->message.size();
}

void ErrorDestroy(PJRT_Error_Destroy_Args* a) { delete a->error; }

PJRT_Error* ErrorCode(PJRT_Error_GetCode_Args* a) {
  a->code = PJRT_Error_Code_INTERNAL;
  return nullptr;
}

PJRT_Error* PluginInitialize(PJRT_Plugin_Initialize_Args*) { return nullptr; }

PJRT_Error* ClientCreate(PJRT_Client_Create_Args* a) {
  const char* n = std::getenv("FAKE_PJRT_DEVICES");
  int num = n ? std::atoi(n) : 4;
  if (num < 1) num = 1;
  auto* c = new PJRT_Client;
  c->devices.resize(num);
  for (int i = 0; i < num; ++i) c->devices[i].id = i;
  for (int i = 0; i < num; ++i) c->device_ptrs.push_back(&c->devices[i]);
  a->client = c;
  return nullptr;
}

PJRT_Error* ClientDestroy(PJRT_Client_Destroy_Args* a) {
  delete a->client;
  return nullptr;
}

PJRT_Error* ClientPlatformName(PJRT_Client_PlatformName_Args* a) {
  a->platform_name = a->client->platform.c_str();
  a->platform_name_size = a->client->platform.size();
  return nullptr;
}

PJRT_Error* ClientAddressableDevices(
    PJRT_Client_AddressableDevices_Args* a) {
  a->addressable_devices = a->client->device_ptrs.data();
  a->num_addressable_devices = a->client->device_ptrs.size();
  return nullptr;
}

size_t TypeBytes(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_F32:
    case PJRT_Buffer_Type_S32:
    case PJRT_Buffer_Type_U32:
      return 4;
    case PJRT_Buffer_Type_BF16:
    case PJRT_Buffer_Type_F16:
      return 2;
    default:
      return 1;
  }
}

PJRT_Error* BufferFromHost(PJRT_Client_BufferFromHostBuffer_Args* a) {
  if (a->num_byte_strides != 0)
    return Err("fake plugin supports only dense layouts");
  size_t n = TypeBytes(a->type);
  for (size_t i = 0; i < a->num_dims; ++i) n *= a->dims[i];
  auto* b = new PJRT_Buffer;
  b->data.assign(static_cast<const unsigned char*>(a->data),
                 static_cast<const unsigned char*>(a->data) + n);
  b->dims.assign(a->dims, a->dims + a->num_dims);
  b->type = a->type;
  b->device_id = a->device ? a->device->id : 0;
  a->buffer = b;
  a->done_with_host_buffer = nullptr;  // completed inline
  return nullptr;
}

PJRT_Error* BufferDestroy(PJRT_Buffer_Destroy_Args* a) {
  delete a->buffer;
  return nullptr;
}

PJRT_Error* BufferToHost(PJRT_Buffer_ToHostBuffer_Args* a) {
  if (a->dst == nullptr) {
    a->dst_size = a->src->data.size();
    return nullptr;
  }
  if (a->dst_size < a->src->data.size()) return Err("dst too small");
  std::memcpy(a->dst, a->src->data.data(), a->src->data.size());
  a->event = nullptr;  // completed inline
  return nullptr;
}

// "FAKE:<n_outputs>" -> loaded executable echoing inputs as outputs.
PJRT_Error* DeserializeAndLoad(PJRT_Executable_DeserializeAndLoad_Args* a) {
  std::string s(a->serialized_executable, a->serialized_executable_size);
  if (s.rfind("FAKE:", 0) != 0)
    return Err("fake plugin can only deserialize FAKE:<n> blobs");
  auto* e = new PJRT_LoadedExecutable;
  e->client = a->client;
  e->n_outputs = std::strtoul(s.c_str() + 5, nullptr, 10);
  if (e->n_outputs == 0) e->n_outputs = 1;
  a->loaded_executable = e;
  return nullptr;
}

PJRT_Error* LoadedDestroy(PJRT_LoadedExecutable_Destroy_Args* a) {
  delete a->executable;
  return nullptr;
}

PJRT_Error* LoadedGetExecutable(
    PJRT_LoadedExecutable_GetExecutable_Args* a) {
  a->executable = new PJRT_Executable{a->loaded_executable->n_outputs};
  return nullptr;
}

PJRT_Error* ExecutableDestroy(PJRT_Executable_Destroy_Args* a) {
  delete a->executable;
  return nullptr;
}

PJRT_Error* ExecutableNumOutputs(PJRT_Executable_NumOutputs_Args* a) {
  a->num_outputs = a->executable->n_outputs;
  return nullptr;
}

PJRT_Error* LoadedAddressableDevices(
    PJRT_LoadedExecutable_AddressableDevices_Args* a) {
  PJRT_Client* c = a->executable->client;
  a->addressable_devices = c->device_ptrs.data();
  a->num_addressable_devices = c->device_ptrs.size();
  return nullptr;
}

// Echo executable: output o of device d is a copy of argument (o % num_args)
// of device d — so the test can verify that per-device argument lists land
// on the right shard slots and outputs come back per device.
PJRT_Error* LoadedExecute(PJRT_LoadedExecutable_Execute_Args* a) {
  PJRT_Client* c = a->executable->client;
  if (a->num_devices != c->device_ptrs.size())
    return Err("Execute num_devices " + std::to_string(a->num_devices) +
               " != client devices " +
               std::to_string(c->device_ptrs.size()));
  const size_t n_out = a->executable->n_outputs;
  for (size_t d = 0; d < a->num_devices; ++d) {
    for (size_t o = 0; o < n_out; ++o) {
      if (a->num_args == 0) return Err("echo executable needs >= 1 arg");
      const PJRT_Buffer* src = a->argument_lists[d][o % a->num_args];
      if (static_cast<size_t>(src->device_id) != d)
        return Err("device " + std::to_string(d) + " got a buffer from device " +
                   std::to_string(src->device_id));
      a->output_lists[d][o] = new PJRT_Buffer(*src);
    }
    if (a->device_complete_events != nullptr)
      a->device_complete_events[d] = nullptr;  // completed inline
  }
  return nullptr;
}

}  // namespace

extern "C" const PJRT_Api* GetPjrtApi() {
  static PJRT_Api api;
  static bool init = false;
  if (!init) {
    std::memset(&api, 0, sizeof(api));
    api.struct_size = PJRT_Api_STRUCT_SIZE;
    api.pjrt_api_version.major_version = PJRT_API_MAJOR;
    api.pjrt_api_version.minor_version = PJRT_API_MINOR;
    api.PJRT_Error_Destroy = ErrorDestroy;
    api.PJRT_Error_Message = ErrorMessage;
    api.PJRT_Error_GetCode = ErrorCode;
    api.PJRT_Plugin_Initialize = PluginInitialize;
    api.PJRT_Client_Create = ClientCreate;
    api.PJRT_Client_Destroy = ClientDestroy;
    api.PJRT_Client_PlatformName = ClientPlatformName;
    api.PJRT_Client_AddressableDevices = ClientAddressableDevices;
    api.PJRT_Client_BufferFromHostBuffer = BufferFromHost;
    api.PJRT_Buffer_Destroy = BufferDestroy;
    api.PJRT_Buffer_ToHostBuffer = BufferToHost;
    api.PJRT_Executable_DeserializeAndLoad = DeserializeAndLoad;
    api.PJRT_LoadedExecutable_Destroy = LoadedDestroy;
    api.PJRT_LoadedExecutable_GetExecutable = LoadedGetExecutable;
    api.PJRT_Executable_Destroy = ExecutableDestroy;
    api.PJRT_Executable_NumOutputs = ExecutableNumOutputs;
    api.PJRT_LoadedExecutable_AddressableDevices = LoadedAddressableDevices;
    api.PJRT_LoadedExecutable_Execute = LoadedExecute;
    init = true;
  }
  return &api;
}
